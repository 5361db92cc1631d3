"""intersect_roofline (%): the least time any exact implementation needs
for the window's N-list intersections (the work model's bytes over the
chip's HBM bandwidth; see harness/workmodel.py) as a share of the device
time of the intersect kernel's ops in the trace.

The kernel's time is summed over the chips (chip-seconds, see
harness/trace_reduce.py), so on several chips the share is the least bytes
over one chip's bandwidth divided by chip-seconds: the least bytes over
the chips' aggregate bandwidth divided by the kernel's time per chip."""
from chipbench.harness import peaks, trace_reduce

# the Pallas calls of kernels/nlist_intersect carry the names of the
# functions that make them: nlist_intersect_pallas (exact) and
# nlist_intersect_pallas_es (early stop)
KERNELS = ("nlist_intersect_pallas",)


def read(run):
    if run.trace is None:
        return None
    ns = trace_reduce.kernel_ns(run.trace, KERNELS)
    work = [op.least_bytes for op in run.ops if op.least_bytes is not None]
    if not ns or not sum(work):
        return None
    least_s = sum(work) / peaks.peaks(run.device_kind)["hbm_bytes_per_s"]
    return 100.0 * least_s / (ns / 1e9)
