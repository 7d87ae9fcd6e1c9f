"""Share of the device's roofline that the chunk programs reach: the
window's steps times the least time one step can take, over the device
time of the program's compiled modules inside the traced window (every
module but the benchmark's own, ``jit_bench_*``, such as the stream
generator).  The least time is the larger of the step's least bytes over
the memory bandwidth and its least operations over the peak rate; both
counts come from ``bench/work/<config>.py``, from shapes."""

OWN = "jit_bench_"


def read(run):
    if run.trace is None or not run.trace["devices"] or run.peaks is None:
        return None
    modules = run.trace["devices"][0]["module_time"]
    program_s = sum(t for name, t in modules.items()
                    if not name.startswith(OWN))
    if program_s <= 0:
        return None
    nbytes, flops = run.work.step(run.cfg)
    t_min = max(nbytes / run.peaks["hbm_bytes_per_s"],
                flops / run.peaks["bf16_flops_per_s"])
    return 100.0 * run.steps * t_min / program_s
