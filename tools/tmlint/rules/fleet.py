"""fleet-transport — fleet wire entry points outside the fleet modules.

ISSUE 18: the verification fleet's wire format (length-prefixed
columnar EntryBlock frames) has exactly three sanctioned homes —
fleet/wire.py (the codec itself), fleet/client.py, and fleet/server.py
(the two endpoints, including their socket-free loopback doubles). The
frame layout is a versioned compatibility surface: a fourth module
encoding frames by hand, or calling the codec directly to smuggle
blocks over its own socket, forks the protocol — version negotiation,
the oversize/malformed containment contract, metrics attribution, and
the flow-continuation discipline all silently stop holding. Same shape
as device-ownership: route through fleet.client.FleetClient (or
LoopbackSession) instead.

Only the fleet codec's OWN entry-point names are flagged — generic
socket calls (sendall et al.) stay legal everywhere because rpc/,
privval/, and p2p/ legitimately own their sockets.
"""

from __future__ import annotations

import ast
from typing import Iterator

from ..core import FileContext, Finding, Rule
from . import func_name

# modules allowed to touch the wire codec (repo-relative)
WHITELIST = frozenset({
    "tendermint_tpu/fleet/wire.py",    # the codec
    "tendermint_tpu/fleet/client.py",  # node-side endpoint + LoopbackSession
    "tendermint_tpu/fleet/server.py",  # fleet-side endpoint + LoopbackFleetHost
})

# the codec's entry points (terminal callee names)
ENTRY_POINTS = frozenset({
    "encode_submit",
    "encode_verdicts",
    "encode_error",
    "parse_frame",
    "send_frame",
    "iter_frames",
    "FrameDecoder",
})


class FleetTransportRule(Rule):
    name = "fleet-transport"
    description = (
        "fleet wire-codec call sites are only legal inside fleet/wire.py, "
        "fleet/client.py, and fleet/server.py"
    )

    def applies_to(self, relpath: str) -> bool:
        return (relpath.startswith("tendermint_tpu/")
                and relpath not in WHITELIST)

    def visit(self, ctx: FileContext) -> Iterator[Finding]:
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            name = func_name(node)
            if name in ENTRY_POINTS:
                yield ctx.finding(
                    self.name, node,
                    f"fleet wire entry point `{name}()` called outside the "
                    f"fleet transport modules — the frame format is a "
                    f"versioned compatibility surface; go through "
                    f"fleet.client.FleetClient (or LoopbackSession) instead",
                )
