"""device-ownership — device-touching entry points outside the dispatcher.

The pipeline is built around a single owner of the device: exactly one
thread — the pipeline's dispatch-owner — may launch kernels, issue device_put
transfers, or upload epoch tables. The module whitelist below is the full
set of modules architecturally sanctioned to hold device-touching code
(the dispatcher itself, the transfer/table implementations, the kernel
definitions, and the direct-path fallbacks in ops/backend.py). A call to
any launch/transfer entry point from ANY other module is a structural
violation: route it through ops.pipeline.AsyncBatchVerifier instead.

The runtime half of this invariant is libs/devcheck.py's device-thread
assertion (TM_TPU_DEVCHECK=1); this pass catches the call SITES the
runtime hooks would only catch when exercised.
"""

from __future__ import annotations

import ast
from typing import Iterator

from ..core import FileContext, Finding, Rule
from . import func_name, receiver_name

# modules allowed to contain device-touching calls (repo-relative)
WHITELIST = frozenset({
    "tendermint_tpu/ops/pipeline.py",      # the dispatch-owner thread
    "tendermint_tpu/ops/device_pool.py",   # transfer() implementation
    "tendermint_tpu/ops/epoch_cache.py",   # lazy table upload (dispatcher-run)
    "tendermint_tpu/ops/backend.py",       # sanctioned direct path + warmup
    "tendermint_tpu/ops/ed25519_verify.py",
    "tendermint_tpu/ops/pallas_verify.py",
    "tendermint_tpu/ops/pallas_rlc.py",
    "tendermint_tpu/ops/pallas_sr25519.py",
    "tendermint_tpu/ops/sharded.py",
    "tendermint_tpu/ops/mesh.py",          # mesh-dispatcher packing + prep
    "tendermint_tpu/ops/mixed.py",
    "tendermint_tpu/ops/bls_verify.py",    # BLS pairing kernel definitions
    "tendermint_tpu/ops/_testing.py",      # test scaffolding, not production
})

# launch / transfer / upload entry points (terminal callee names)
ENTRY_POINTS = frozenset({
    "device_put",
    "copy_to_host_async",
    "block_until_ready",
    "jitted_verify",
    "jitted_verify_cached",
    "select_kernel",
    "rlc_launch",
    "cached_kernel",
    "rlc_cached_fn",
    "cached_compact_fn",
    "_jitted_rlc_verify",
    "_jitted_rlc_verify_slot_major",
    "_jitted_pallas_verify",
    "verify_kernel_cached",
    "xla_tables",
    "coords_tables",
    # mesh dispatcher (ISSUE 9): superbatch launch builders + the
    # replicated epoch-table uploads
    "mesh_valid_fn",
    "mesh_valid_fn_cached",
    "mesh_pallas_valid_fn",
    "epoch_tables_sharded",
    "sharded_xla_tables",
    "prepare_superbatch",
    # BLS aggregation lane (ISSUE 20): the fused multi-pairing launch
    # builders and the direct code-row path — aggregated commits must
    # reach the device through AsyncBatchVerifier / the mesh, never by
    # jitting the pairing kernels at the call site
    "jitted_bls_verify",
    "jitted_bls_finalexp",
    "bls_kernel",
    "verify_batch_bls_codes",
    # mocked-device doubles (ISSUE 11): these REPLACE the device for
    # benches/gates — production code (the light service's dispatch path
    # included) must route through AsyncBatchVerifier, never wire a mock
    "mock_light_prepare",
    "mock_mesh_prepare",
    "mock_mempool_prepare",
    "mock_vote_prepare",
    "slow_prepare",
    "slow_mesh_prepare",
})

# `transfer` is a common word; only flag it on a device_pool-ish receiver
_QUALIFIED = {"transfer": ("_dpool", "device_pool", "dpool", "pool")}


class DeviceOwnershipRule(Rule):
    name = "device-ownership"
    description = (
        "kernel-launch / device_put / epoch-table-upload call sites are "
        "only legal inside the dispatcher module whitelist"
    )

    def applies_to(self, relpath: str) -> bool:
        return (relpath.startswith("tendermint_tpu/")
                and relpath not in WHITELIST)

    def visit(self, ctx: FileContext) -> Iterator[Finding]:
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            name = func_name(node)
            hit = name in ENTRY_POINTS
            if not hit and name in _QUALIFIED:
                hit = receiver_name(node) in _QUALIFIED[name]
            if hit:
                yield ctx.finding(
                    self.name, node,
                    f"device entry point `{name}()` called outside the "
                    f"dispatcher whitelist — only the single dispatch-owner "
                    f"thread (ops/pipeline.py) may touch the device; submit "
                    f"through AsyncBatchVerifier instead",
                )
