"""Gather kernels (kernels/gather_distance.py): the least time the bytes the
algorithm needs could take at the chip's peak bandwidth, over the summed
device time of the gather-kernel events, in percent. The bytes are those of
the traced requests: ``n_comps`` rows of the configuration's width and dtype
plus one int32 id each (``bench/kernels.py``)."""
from bench.kernels import gather_bytes, is_gather_kernel


def read(run):
    if run.trace is None:
        return None
    traced = [r for r in run.record.get("requests", ()) if r["traced"]]
    kernel_s = run.trace.device_time_s(is_gather_kernel)
    if not traced or not kernel_s:
        return None
    nbytes = sum(gather_bytes(int(r["n_comps"].sum()), run.config)
                 for r in traced)
    return 100.0 * nbytes / run.peaks["hbm_bytes_per_s"] / kernel_s
