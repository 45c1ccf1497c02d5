"""Roofline share of the fused SSA Pallas kernel on packed operands.

Packed (uint32 bit-plane) operands: the decode and chunked-prefill calls.

The least time the chip needs for the calls' work (the larger of
operations over the bf16 peak and bytes over the HBM bandwidth, per call,
from ``bench/counts.py`` at logical shapes) over the kernel's device
time in the traced window.  The kernel is the ``ssa_attention`` custom
call whose query operand (the 4th) is u32.
"""
import sys

from bench import trace_reduce, work

QUERY_DTYPES = ("u32",)


def _kernel(e):
    if trace_reduce.op_base(e) != "ssa_attention":
        return False
    dtypes = trace_reduce.call_operand_dtypes(e)
    return len(dtypes) > 3 and dtypes[3] in QUERY_DTYPES


def read(run):
    if run.trace is None:
        return None
    share, bound = work.roofline_share(run, run.trace.op_seconds(_kernel))
    if share is not None:
        print(f"bench: kernel.ssa_packed_roofline bound by {bound}", file=sys.stderr)
    return share
