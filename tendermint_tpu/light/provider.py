"""Light-block providers.

Reference parity: light/provider/ — the Provider interface (LightBlock,
ReportEvidence) and concrete implementations. The reference's primary
implementation fetches over RPC (provider/http); here the equivalent
node-backed provider reads another node's stores directly (the in-process
analog used by tests and statesync) and the RPC-backed provider lands with
the RPC client.
"""

from __future__ import annotations

import abc
from dataclasses import dataclass
from typing import Optional

from ..observability import trace as _trace
from ..types import Commit, Header, SignedHeader, ValidatorSet
from ..wire.proto import ProtoWriter, decode_message, field_bytes

_span = _trace.span


@dataclass
class LightBlock:
    """types.LightBlock: SignedHeader + its validator set."""

    signed_header: SignedHeader
    validators: ValidatorSet

    @property
    def height(self) -> int:
        return self.signed_header.header.height

    def hash(self) -> bytes:
        return self.signed_header.header.hash()

    def encode(self) -> bytes:
        """tendermint.types.LightBlock: 1 signed_header, 2 validator_set."""
        w = ProtoWriter()
        w.write_message(1, self.signed_header.encode(), always=True)
        w.write_message(2, self.validators.encode(), always=True)
        return w.bytes()

    @classmethod
    def decode(cls, data: bytes) -> "LightBlock":
        """What a light client is handed for a height: the signed header
        and the validator set that signed it. Both parts are required
        (types/light.go LightBlockFromProto); the set's decode validates
        it, the header's checks are verify_adjacent's."""
        f = decode_message(data)
        if 1 not in f:
            raise ValueError("missing signed header")
        if 2 not in f:
            raise ValueError("missing validator set")
        signed_header = SignedHeader.decode(field_bytes(f, 1))
        with _span("light.decode.valset"):
            validators = ValidatorSet.decode(field_bytes(f, 2))
        return cls(signed_header=signed_header, validators=validators)


class ErrLightBlockNotFound(KeyError):
    pass


class Provider(abc.ABC):
    @abc.abstractmethod
    def light_block(self, height: int) -> LightBlock:
        """Fetch the light block at height (0 = latest). Raises
        ErrLightBlockNotFound when unavailable."""

    def report_evidence(self, ev) -> None:  # noqa: B027 — optional hook
        pass


class HTTPProvider(Provider):
    """light/provider/http: fetches signed headers + validator sets from a
    node's JSON-RPC endpoint (/commit, /validators with pagination)."""

    def __init__(self, base_url: str, timeout: float = 10.0):
        self._url = base_url.rstrip("/")
        for prefix in ("tcp://",):
            if self._url.startswith(prefix):
                self._url = "http://" + self._url[len(prefix):]
        if not self._url.startswith("http"):
            self._url = "http://" + self._url
        self._timeout = timeout

    def _get(self, path: str) -> dict:
        import json as _json
        import urllib.request

        with urllib.request.urlopen(
            f"{self._url}/{path}", timeout=self._timeout
        ) as r:
            res = _json.loads(r.read())
        if "error" in res and res["error"]:
            raise ErrLightBlockNotFound(res["error"])
        return res["result"]

    def light_block(self, height: int) -> LightBlock:
        from ..wire.json_types import parse_signed_header, parse_validator_set

        try:
            q = f"?height={height}" if height else ""
            com = self._get(f"commit{q}")
            sh = parse_signed_header(com["signed_header"])
            h = sh.header.height
            vals = []
            page = 1
            while True:
                res = self._get(f"validators?height={h}&page={page}&per_page=100")
                got = res["validators"]
                if not got:
                    # a byzantine primary could promise total=N forever;
                    # an empty page means it cannot deliver — stop
                    raise ErrLightBlockNotFound(f"empty validator page {page}")
                vals.extend(got)
                if len(vals) >= int(res["total"]) or page >= 100:
                    break
                page += 1
            vset = parse_validator_set({"validators": vals})
        except (OSError, ValueError, KeyError) as e:
            raise ErrLightBlockNotFound(str(e)) from e
        return LightBlock(signed_header=sh, validators=vset)

    def report_evidence(self, ev) -> None:
        import base64 as _b64
        import urllib.parse
        import urllib.request

        from ..types.evidence import encode_evidence

        # percent-encode: raw base64 '+' would decode as a space in the
        # server's query parser and silently corrupt the evidence
        data = urllib.parse.quote(_b64.b64encode(encode_evidence(ev)).decode())
        try:
            urllib.request.urlopen(
                f"{self._url}/broadcast_evidence?evidence=%22{data}%22",
                timeout=self._timeout,
            ).read()
        except OSError:
            pass  # best effort (detector.go sendEvidence)


class NodeBackedProvider(Provider):
    """Reads block store + state store of a (local) node."""

    def __init__(self, block_store, state_store):
        self._bs = block_store
        self._ss = state_store

    def light_block(self, height: int) -> LightBlock:
        if height == 0:
            height = self._bs.height()
        meta = self._bs.load_block_meta(height)
        commit = self._bs.load_block_commit(height)
        if commit is None and height == self._bs.height():
            # at the tip only the seen commit exists (core/blocks.go Commit)
            seen = self._bs.load_seen_commit()
            if seen is not None and seen.height == height:
                commit = seen
        if meta is None or commit is None:
            raise ErrLightBlockNotFound(height)
        try:
            vals = self._ss.load_validators(height)
        except KeyError as e:
            raise ErrLightBlockNotFound(height) from e
        return LightBlock(
            signed_header=SignedHeader(header=meta.header, commit=commit),
            validators=vals,
        )
