"""Operations and bytes of the simulator's kernels, and the chip's peaks.

Each function gives the work of ONE call of a kernel for ONE run and ONE
slot, at the shapes the engine calls the kernel's wrapper with; padding
the wrapper adds is left out, because the padded arrays need not come from
HBM (the compiler may keep them in on-chip memory). The harness multiplies
by the run-slots executed in the traced window.

Operations count the float arithmetic per candidate pair or element on the
vector unit: a squared distance is 2 subtractions, 2 multiplications and
1 addition, and its radius compare and candidate compare are 2 more. Bytes
count each operand read once and each result written once.
"""

from __future__ import annotations

import json
import os

#: Float operations per candidate pair: d2 = dx*dx + dy*dy, d2 <= r2, and
#: the running-minimum compare.
PAIR_OPS = 7
#: Float operations per element of a row merge: w*own + (1-w)*peer.
MERGE_OPS = 4
F32 = 4

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown device is an
    error, never a default."""
    with open(_PEAKS) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(f"no peaks known for device kind {device_kind!r}")
    return table[device_kind]


def roofline_share(ops: float, nbytes: float, seconds: float,
                   device_kind: str) -> float:
    """Percent of the roofline: the least time the chip could take for
    ``ops`` and ``nbytes`` (the larger of the two bounds) over
    ``seconds``."""
    pk = peaks(device_kind)
    least = max(ops / pk["flops_per_s"], nbytes / pk["hbm_bytes_per_s"])
    return 100.0 * least / seconds


def pairwise_contacts(n_nodes: int) -> tuple[int, int]:
    """Dense pairwise-contact pass over ``n_nodes`` (one run, one slot):
    every node meets every node. In: positions ``(N, 2)``, zone words,
    eligibility and the previous close words ``(N, ceil(N / 32))``; out:
    the close words, the best candidate and its flag."""
    words = n_nodes * (-(-n_nodes // 32))
    ops = PAIR_OPS * n_nodes * n_nodes
    nbytes = F32 * (4 * n_nodes + 2 * words + 2 * n_nodes)
    return ops, nbytes


def gossip_merge_rows(n_nodes: int, dim: int) -> tuple[int, int]:
    """Row-wise merge of ``(n_nodes, dim)`` replicas with their peers'
    (one run, one slot). In: own, peer, and a weight and a flag per row;
    out: the merged rows."""
    ops = MERGE_OPS * n_nodes * dim
    nbytes = F32 * (3 * n_nodes * dim + 2 * n_nodes)
    return ops, nbytes
