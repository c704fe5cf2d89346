"""The Pallas SSD kernel's calls in a traced window.

The compiled step holds each Pallas call as an HLO custom call with target
``tpu_custom_call``, under an instruction name of XLA's choosing, and the
device trace names its events by those instructions.  Each such call carries
its kernel as a serialized Mosaic module in ``backend_config``, and that
module keeps the name of the Pallas body it was lowered from:
``_ssd_chunk_kernel`` for the forward, ``_ssd_chunk_bwd_kernel`` for the
backward (``kernels/ssd_scan.py``).  The calls are told apart by that name,
and the trace's events are matched to their instructions' names.
"""
from __future__ import annotations

import base64
import json
import re

import tracereduce

BODIES = {"fwd": b"_ssd_chunk_kernel", "bwd": b"_ssd_chunk_bwd_kernel"}
_CALL = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?custom-call\(.*"
                   r"custom_call_target=\"tpu_custom_call\".*?"
                   r"backend_config=(\{.*)$")
_NAME = re.compile(rb"[A-Za-z_]\w*")


def _body(config: str) -> bytes:
    """The Mosaic module of a ``tpu_custom_call``'s ``backend_config``."""
    obj, _ = json.JSONDecoder().raw_decode(config)
    return base64.b64decode(obj["custom_call_config"]["body"])


def kernel_ops(hlo_text: str) -> dict:
    """{instruction name: "fwd" | "bwd"} for the compiled step's SSD calls."""
    out = {}
    for line in hlo_text.splitlines():
        m = _CALL.match(line)
        if not m:
            continue
        names = set(_NAME.findall(_body(m.group(2))))
        for kind, body in BODIES.items():
            if body in names:
                out[m.group(1)] = kind
    return out


def calls(ctx: dict) -> dict:
    """{kind: (device seconds averaged over devices, calls per device)}."""
    lo, hi, devices = ctx["lo"], ctx["hi"], ctx["devices"]
    named = kernel_ops(ctx["hlo_text"])
    out = {}
    for kind in BODIES:
        names = {n for n, k in named.items() if k == kind}
        hits = [tracereduce.kernel_time(
            [o for o in ops if o.name in names], "", lo, hi)
            for ops in devices]
        out[kind] = (sum(h[0] for h in hits) / len(hits),
                     sum(h[1] for h in hits) / len(hits))
    return out
