"""MXU share of the support_count kernel launches in the traced mines:
the device ops named ``support_count_pallas`` (the int8-matmul kernel)
over every device op named ``support_count*`` (with the packed kernel,
``support_count_fused_pallas``).  A trace that holds no support_count
kernel, as on a plane that counts with the jitted reference, leaves
nothing to read."""
from mba_bench import trace

MXU_OP = "support_count_pallas"
KERNEL_PREFIX = "support_count"


def read(run):
    if run.trace is None or not getattr(run.loop, "traced", 0):
        return None
    counts = trace.op_counts(run.trace)
    total = sum(n for name, n in counts.items()
                if name.startswith(KERNEL_PREFIX))
    return counts.get(MXU_OP, 0) / total if total else None
