"""Share of the paged gather kernel's device time that its roofline allows:
``Σ least time / Σ time`` over its calls in the traced round.  The gather
moves data and computes nothing: a call's least time is its bytes over the
HBM bandwidth, the block table read plus each gathered block read once and
written once into the view.

The kernel has no name of its own in the trace yet (it runs under
``vmap``), so its calls are known by their signature: the one Pallas call
whose first operand is a 2-D int32 block table and whose result has the
table's two dimensions among its leading ones."""

from harness import hlo_shapes


def is_gather(arrays) -> bool:
    if len(arrays) != 3:
        return False
    out, table, _ = arrays
    dims = out[1]
    return (table[0] == "s32" and len(table[1]) == 2 and any(
        dims[i:i + 2] == table[1] for i in range(len(dims) - 1)))


def least_time(arrays, peaks):
    if len(arrays) < 3:
        return None
    out, table = arrays[0], arrays[1]
    nbytes = hlo_shapes.nbytes(table) + 2 * hlo_shapes.nbytes(out)
    return nbytes / peaks["hbm_bytes_per_s"]


def read(run):
    if run.trace is None or not run.peaks:
        return None
    calls = run.trace.ops(lambda e: is_gather(hlo_shapes.call_arrays(e)))
    return hlo_shapes.roofline_share(calls, lambda a: least_time(a, run.peaks))
