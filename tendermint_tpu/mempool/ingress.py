"""Device-batched transaction ingress (ISSUE 13).

The second serving workload from the north star: user transactions.
`check_tx` used to be a pure host path — the signed-tx envelope below
adds signature-carrying txs, and this module's accumulator batches their
signatures into `EntryBlock`s over a short time/size window and submits
them to the SHARED AsyncBatchVerifier at INGRESS priority, so a tx flood
rides the device pipeline (thousands of sigs per device launch) without
ever starving consensus commit batches (ops/pipeline.py QoS classes).

Signed-tx envelope (scheme-tagged, nonce-carrying):

    MAGIC(4) | scheme(1) | pub(32|33) | nonce(8 BE) | sig(64) | payload

The signed message is the envelope minus the signature field (MAGIC +
scheme + pub + nonce + payload) — a signature cannot be transplanted
onto a different payload, nonce or key. Txs WITHOUT the magic (the
kvstore's `k=v` and `val:` txs, every pre-existing test fixture) carry
no signature and bypass the verification stage entirely: their CheckTx
responses are byte-identical to the pre-ISSUE-13 behavior.

Scheme lanes (the 2302.00418 story):
  ed25519    device lane — batched through the shared verifier
  sr25519    host batch lane — crypto/sr25519.verify_batch (the native
             schnorrkel batch path when built); schnorrkel's transcript
             binding has no device kernel here yet
  secp256k1  host fallback, one ECDSA verify per tx on the completion
             thread — batched ECDSA verification is the documented gap
             (README "Transaction ingress"); NEVER silently dropped: an
             unverifiable sig is an explicit rejection, not an accept.

Threading (the deadlock rule this module exists to respect): completion
work that takes the mempool lock runs on the ingress fabric's completer
thread, never on the pipeline's resolver thread — consensus holds the
mempool lock across update()→recheck while waiting on pipeline futures,
so a resolver blocked on that lock would deadlock the process. Verifier
done-callbacks only enqueue; the completer does the locking.

Since ISSUE 17 the windowing machinery itself lives in ops/ingress.py
(the one ingress fabric): this module keeps the envelope format, the
host-stage scheme routing, and the verdict-future delivery — a LaneSpec
plus callbacks. Knobs: TM_TPU_INGRESS_MEMPOOL_BATCH / _WINDOW_MS
(legacy TM_TPU_MEMPOOL_BATCH / TM_TPU_MEMPOOL_WINDOW_MS still honored
with a DeprecationWarning).
"""

from __future__ import annotations

import weakref
from concurrent.futures import Future
from typing import Dict, List, Optional, Sequence

from ..ops import ingress as _fabric

MAGIC = b"\xc1TX1"
SCHEME_ED25519 = 0
SCHEME_SR25519 = 1
SCHEME_SECP256K1 = 2
_PUB_LEN = {SCHEME_ED25519: 32, SCHEME_SR25519: 32, SCHEME_SECP256K1: 33}
_SIG_LEN = 64
_NONCE_LEN = 8

DEFAULT_BATCH = 256
DEFAULT_WINDOW_MS = 4.0


class MalformedTxError(ValueError):
    """Envelope magic present but the structure is broken (truncated
    fields, unknown scheme). A ValueError so the reactor/RPC catch sites
    that already reject bad txs reject these too."""


class SignedTx:
    __slots__ = ("scheme", "pub", "nonce", "sig", "payload", "raw")

    def __init__(self, scheme: int, pub: bytes, nonce: int, sig: bytes,
                 payload: bytes, raw: bytes):
        self.scheme = scheme
        self.pub = pub
        self.nonce = nonce
        self.sig = sig
        self.payload = payload
        self.raw = raw

    def signed_bytes(self) -> bytes:
        """The message the signature covers: the envelope minus the
        signature field."""
        return (MAGIC + bytes([self.scheme]) + self.pub
                + self.nonce.to_bytes(_NONCE_LEN, "big") + self.payload)


def parse_signed_tx(tx: bytes) -> Optional[SignedTx]:
    """None when `tx` carries no envelope (legacy tx — no sig stage);
    MalformedTxError when the magic is present but the layout is not."""
    if not tx.startswith(MAGIC):
        return None
    if len(tx) < len(MAGIC) + 1:
        raise MalformedTxError("signed tx truncated before scheme byte")
    scheme = tx[len(MAGIC)]
    pub_len = _PUB_LEN.get(scheme)
    if pub_len is None:
        raise MalformedTxError(f"unknown signature scheme {scheme}")
    hdr = len(MAGIC) + 1 + pub_len + _NONCE_LEN + _SIG_LEN
    if len(tx) < hdr:
        raise MalformedTxError(
            f"signed tx truncated: {len(tx)} < {hdr} header bytes"
        )
    off = len(MAGIC) + 1
    pub = tx[off : off + pub_len]
    off += pub_len
    nonce = int.from_bytes(tx[off : off + _NONCE_LEN], "big")
    off += _NONCE_LEN
    sig = tx[off : off + _SIG_LEN]
    off += _SIG_LEN
    return SignedTx(scheme, pub, nonce, sig, tx[off:], tx)


def encode_signed_tx(scheme: int, pub: bytes, nonce: int, sig: bytes,
                     payload: bytes) -> bytes:
    if len(pub) != _PUB_LEN[scheme]:
        raise ValueError(f"scheme {scheme} pubkey must be "
                         f"{_PUB_LEN[scheme]} bytes, got {len(pub)}")
    if len(sig) != _SIG_LEN:
        raise ValueError(f"signature must be {_SIG_LEN} bytes")
    return (MAGIC + bytes([scheme]) + pub
            + int(nonce).to_bytes(_NONCE_LEN, "big") + sig + payload)


def make_signed_tx(priv, payload: bytes, nonce: int,
                   scheme: int = SCHEME_ED25519) -> bytes:
    """Sign `payload` under the envelope: the signature covers the full
    header (scheme, pub, nonce) plus the payload."""
    pub = priv.pub_key().bytes()
    body = (MAGIC + bytes([scheme]) + pub
            + int(nonce).to_bytes(_NONCE_LEN, "big") + payload)
    sig = priv.sign(body)
    return encode_signed_tx(scheme, pub, nonce, sig, payload)


def host_verify(stx: SignedTx) -> bool:
    """Per-scheme host verification — the sequential baseline (no
    accumulator attached) and the recheck fallback for host-lane schemes.
    An unverifiable signature (missing native backend, structurally bad
    key) is False — an explicit rejection — never a silent accept."""
    msg = stx.signed_bytes()
    try:
        if stx.scheme == SCHEME_ED25519:
            from ..crypto import ed25519 as _ed

            return bool(_ed.verify_zip215_fast(stx.pub, msg, stx.sig))
        if stx.scheme == SCHEME_SR25519:
            from ..crypto import sr25519 as _sr

            return bool(_sr.verify_batch([(stx.pub, msg, stx.sig)])[0])
        if stx.scheme == SCHEME_SECP256K1:
            from ..crypto import secp256k1 as _secp

            return bool(_secp.PubKey(stx.pub).verify_signature(msg, stx.sig))
    except Exception:  # noqa: BLE001 — reject, never crash CheckTx
        return False
    return False


# live accumulators for /status aggregation (rpc/core.py)
_ACTIVE: "weakref.WeakSet[IngressAccumulator]" = weakref.WeakSet()


def ingress_stats() -> dict:
    """Aggregate snapshot over every live accumulator in the process —
    the /status `mempool_ingress` section."""
    accs = list(_ACTIVE)
    if not accs:
        return {"enabled": False}
    out: Dict[str, float] = {
        "enabled": True, "queue_depth": 0, "batches": 0, "sigs": 0,
        "host_lane_sigs": 0, "preemptions": 0, "dispatch_errors": 0,
    }
    waits = []
    for a in accs:
        s = a.stats()
        out["queue_depth"] += s["queue_depth"]
        out["batches"] += s["batches"]
        out["sigs"] += s["sigs"]
        out["host_lane_sigs"] += s["host_lane_sigs"]
        out["preemptions"] += s["preemptions"]
        out["dispatch_errors"] += s["dispatch_errors"]
        if s["batch_wait_ms_avg"]:
            waits.append(s["batch_wait_ms_avg"])
    out["batch_wait_ms_avg"] = sum(waits) / len(waits) if waits else 0.0
    return out


class IngressAccumulator:
    """Window/size-batched CheckTx signature verification — a `mempool`
    lane on the shared ingress fabric (ops/ingress.py).

    submit(stx) returns a Future[bool] sig verdict. ed25519 entries
    accumulate until the lane's batch target or window elapses, then
    flush as ONE EntryBlock into the shared verifier at
    PRIORITY_INGRESS; sr25519/secp256k1 entries flush on the same clock
    through their host lanes. Verdict futures resolve on the fabric's
    completer thread (see the module docstring for why that thread
    exists). A DispatchError from the device poisons ONLY its own
    window's futures — later windows are untouched.

    Explicit max_batch/window_ms pin the window (deterministic, the
    pre-fabric behavior); defaulted knobs get the adaptive SLO-aware
    controller unless TM_TPU_INGRESS_MEMPOOL_ADAPTIVE says otherwise."""

    def __init__(self, verifier=None, max_batch: Optional[int] = None,
                 window_ms: Optional[float] = None, metrics=None):
        cfg = _fabric.resolve_lane_config(
            "mempool", batch=max_batch, window_ms=window_ms,
            legacy_batch="TM_TPU_MEMPOOL_BATCH",
            legacy_window="TM_TPU_MEMPOOL_WINDOW_MS",
        )
        self.metrics = metrics
        self._lane = _fabric.shared_engine().register(_fabric.LaneSpec(
            name="mempool",
            priority=_fabric.PRIORITY_INGRESS,
            batch=cfg.batch,
            window_ms=cfg.window_ms,
            budget_ms=cfg.budget_ms,
            adaptive=cfg.adaptive,
            use_completer=True,      # delivery may take the mempool lock
            closed_msg="ingress accumulator is closed",
            verifier=verifier,
            entries_fn=lambda s: (s.pub, s.signed_bytes(), s.sig),
            route_fn=lambda s: s.scheme == SCHEME_ED25519,
            host_fn=self._host_check,
            deliver=self._deliver,
            observer=self,
        ))
        _ACTIVE.add(self)

    # -- lane callbacks ---------------------------------------------------

    def _deliver(self, items, verdicts, err) -> None:
        """Resolve the per-tx verdict futures (fabric completer thread).
        A window error fails exactly these futures — poisoned-window
        isolation, the txs stay retryable upstream."""
        if err is not None:
            for it in items:
                if not it.future.done():
                    it.future.set_exception(err)
            return
        for it, ok in zip(items, verdicts):
            if not it.future.done():
                it.future.set_result(bool(ok))

    def _host_check(self, stxs: List[SignedTx]) -> Sequence[bool]:
        """Host-lane verification in item order: sr25519 as one native
        batch (schnorrkel when built), secp256k1 (and anything future)
        per-sig — the explicit non-batched path, never a silent drop."""
        verdicts: List[bool] = [False] * len(stxs)
        sr_idx = [i for i, s in enumerate(stxs)
                  if s.scheme == SCHEME_SR25519]
        if sr_idx:
            try:
                from ..crypto import sr25519 as _sr

                vs = _sr.verify_batch(
                    [(stxs[i].pub, stxs[i].signed_bytes(), stxs[i].sig)
                     for i in sr_idx]
                )
            except Exception:  # noqa: BLE001 — reject, never drop
                vs = [False] * len(sr_idx)
            for i, ok in zip(sr_idx, vs):
                verdicts[i] = bool(ok)
        for i, s in enumerate(stxs):
            if s.scheme != SCHEME_SR25519:
                verdicts[i] = host_verify(s)
        return verdicts

    # -- legacy metric mirror (fabric observer) ---------------------------

    def _metrics(self):
        if self.metrics is None:
            from ..libs import metrics as _m

            self.metrics = _m.mempool_metrics()
        return self.metrics

    def depth(self, d: int) -> None:
        self._metrics().ingress_queue_depth.set(d)

    def flush(self, n: int, wait_ms: float) -> None:
        m = self._metrics()
        m.ingress_queue_depth.set(0)
        m.ingress_batch_wait_ms.observe(wait_ms)

    def preempt(self, n: int) -> None:
        self._metrics().checktx_preemptions.inc(n)

    # -- public API -------------------------------------------------------

    def submit(self, stx: SignedTx) -> "Future[bool]":
        """Queue one signature; the returned future resolves to the bool
        verdict (or raises DispatchError when the device window failed)
        on the fabric completer thread."""
        return self._lane.submit(stx, want_future=True)

    def submit_block(self, block, priority: Optional[int] = None):
        """Raw EntryBlock passthrough for recheck: returns the PIPELINE
        future directly (resolved on the resolver thread, which never
        takes the mempool lock) — safe to wait on while holding the
        mempool lock, unlike the per-tx futures from submit()."""
        return self._lane.submit_block(block, priority=priority,
                                       count=False)

    def flush_now(self) -> None:
        self._lane.flush_now()

    def stats(self) -> dict:
        s = self._lane.stats()
        return {k: s[k] for k in (
            "queue_depth", "batches", "sigs", "host_lane_sigs",
            "batch_wait_ms_avg", "preemptions", "dispatch_errors",
            "max_batch", "window_ms",
        )}

    def close(self, timeout: float = 10.0) -> None:
        self._lane.close(timeout=timeout)
