"""Share of the group-by sum kernel's roofline, in percent: the least time
the group-by sums of the window's answers need, their bytes
(``bench.peaks.groupby_sum_bytes``, from each program's
``groupby_sums``) over the device's HBM bandwidth, divided by the device
time of the kernel's events in the calls that ran it.

Each call is found on the trace by the harness's ``bench:<program>``
annotation; a call whose program declares no group-by sums still adds its
kernel time.  The kernel is the Pallas call of ``_groupby_sum`` in
``repro/kernels/groupby_sum.py``: on the TPU's trace, a ``tpu_custom_call``
instruction named after it."""
from bench.peaks import groupby_sum_bytes, peaks

KERNEL = "_groupby_sum"


def is_kernel(event) -> bool:
    return (event.name.split(".")[0] == KERNEL
            and "tpu_custom_call" in event.stats.get("long_name", ""))


def read(run):
    if run.trace is None:
        return None
    hbm = peaks(run.device_kind)["hbm_bytes_per_s"]
    need: dict[str, float] = {}
    least = spent = 0.0
    for name, lo, hi in run.trace.program_calls():
        kernel = [e for e in run.trace.ops_between(lo, hi) if is_kernel(e)]
        if not kernel:
            continue
        spent += sum(e.seconds for e in kernel)
        if name not in need:
            work = getattr(run.programs[name], "groupby_sums", None)
            need[name] = sum(groupby_sum_bytes(*w)
                             for w in work(run.tables)) if work else 0
        least += need[name] / hbm
    return 100.0 * least / spent if spent else None
