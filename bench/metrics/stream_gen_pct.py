"""Share of the device's busy time spent in the benchmark's own stream
generator, the compiled program named ``bench_stream_gen``."""

NAME = "jit_bench_stream_gen"


def read(run):
    if run.trace is None or not run.trace["devices"]:
        return None
    dev = run.trace["devices"][0]
    if NAME not in dev["module_time"] or dev["busy_s"] <= 0:
        return None
    return 100.0 * dev["module_time"][NAME] / dev["busy_s"]
