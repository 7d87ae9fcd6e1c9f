"""One run of one benchmark cell, found by name in ``BENCHMARK.json``.

A cell names a configuration and a traffic mix.  Everything the run needs
is found from those names, so a new cell is a new file and a new entry:

* ``configs[].file``: the configuration (JSON) -- sizes, the reference's
  parameters, the limits of the correctness check, and ``family``, which
  names the module in ``bench/families/`` that builds the program's
  learner and holds the plain reference (``bench/ref/``) beside it;
* ``bench/traffic/<mix>.json``: what the window drives -- its feed of
  chunks (``bench/feeds/<feed>.py``), and any predict load
  (``bench/load.py``, arrivals from ``bench/arrivals/<kind>.py``);
* ``bench/metrics/<metric>.py``: one reader per metric, end-to-end or
  per-layer; it returns the number, or None where it finds nothing;
* ``bench/work/<config>.py``: the least bytes and operations one step of
  the configuration needs, for roofline shares;
* ``bench/peaks.json``: the peaks of each device kind.

A configuration may give ``mesh``, named axes and their sizes in the
program's convention (``{"model": 4}``: attributes split over four
chips); its product is the workload's ``chips``.  Without it the
configuration runs on one chip.  One chip runs the program's
``JitEngine`` on the default device; more run its ``ShardMapEngine`` over
a mesh of the first ``chips`` devices, the feed draws every chunk
replicated on that mesh, and the family's reference is given the mesh
(``ref_init(cfg, mesh=mesh)``) to place its state over the same chips.

A run: set-up (compile cache, warm-up of the cell's own shapes), a window
of whole chunks of prequential training -- ``seconds`` times the
configuration's ``chunks_per_s``, a fixed amount of work -- with the
mix's predict load beside it, then -- after the window and after the peak
memory has been read -- the plain reference replays the same stream and
the program's final state, prequential metric and served answers are
compared with it.
"""

from __future__ import annotations

import gc
import importlib
import importlib.util
import json
import math
import os
import shutil
import sys
import time
from collections import defaultdict
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent.parent
BENCH = ROOT / "bench"
CACHE = ROOT / ".bench_cache"


class NoDevice(RuntimeError):
    """The chips the cell asks for are not there."""


def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _applies(metric: dict, workload: str, e2e_names) -> bool:
    if "workloads" in metric:
        return workload in metric["workloads"]
    return e2e_names is None or metric["moves"] in e2e_names


class Cell:
    """A cell's configuration, traffic, metrics and modules, by name."""

    def __init__(self, workload: str, *, benchmark: dict | None = None,
                 overrides: dict | None = None,
                 predict_overrides: dict | None = None):
        bm = benchmark or load_json(ROOT / "BENCHMARK.json")
        found = [w for w in bm["workloads"] if w["name"] == workload]
        if not found:
            raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
        w = found[0]
        self.name = workload
        self.chips = int(w["chips"])
        entry = [c for c in bm["configs"] if c["name"] == w["config"]][0]
        self.config_name = entry["name"]
        self.cfg = load_json(ROOT / entry["file"])
        self.cfg.update(overrides or {})
        self.traffic = load_json(BENCH / "traffic" / f"{w['traffic']}.json")
        if predict_overrides:
            self.traffic["predict"] = dict(self.traffic["predict"],
                                           **predict_overrides)
        self.mesh_shape = dict(self.cfg.get("mesh") or {})
        laid_over = math.prod(self.mesh_shape.values())
        if laid_over != self.chips:
            raise ValueError(
                f"workload {workload!r} asks for {self.chips} chips, but "
                f"configuration {self.config_name!r} is laid over "
                f"{laid_over} (mesh {self.mesh_shape or 'none'})")
        if self.chips > 1 and (self.traffic.get("publish")
                               or self.traffic.get("predict")):
            raise NotImplementedError(
                f"workload {workload!r}: publishing or serving on "
                f"{self.chips} chips is not supported")
        self.family = importlib.import_module(
            f"bench.families.{self.cfg['family']}")
        self.end_to_end = [m for m in bm["end_to_end"]
                           if _applies(m, workload, None)]
        names = {m["name"] for m in self.end_to_end}
        self.per_layer = [m for m in bm["per_layer"]
                          if _applies(m, workload, names)]

    def work(self):
        return load_module(BENCH / "work" / f"{self.config_name}.py")


def seed_key(seed: int):
    """A PRNG key from any whole number (the driver's exceed 32 bits)."""
    import jax
    seed = int(seed) % (1 << 62)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


class CompileCounter:
    """Counts compilations: persistent-cache lookups (one per program
    compiled or loaded) and backend compiles; reports the larger."""

    EVENTS = ("/jax/compilation_cache/compile_requests_use_cache",)
    DURATIONS = ("/jax/core/compile/backend_compile_duration",)

    def __init__(self):
        import jax
        self.requests = 0
        self.backend = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **kw):
        if name in self.EVENTS:
            self.requests += 1

    def _duration(self, name, secs, **kw):
        if name in self.DURATIONS:
            self.backend += 1

    def count(self) -> int:
        return max(self.requests, self.backend)


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at ``$JAX_COMPILATION_CACHE_DIR``
    when set, else at a fixed path inside the checkout."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE / "jax")
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def require_chips(n: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoDevice(f"needs a TPU; JAX found {devs[0].platform}")
    if len(devs) < n:
        raise NoDevice(f"needs {n} chips; JAX found {len(devs)}")


def make_mesh(cell: Cell):
    """The cell's mesh over its first ``chips`` devices, with the
    configuration's axes; None on one chip."""
    if cell.chips == 1:
        return None
    from repro.launch.mesh import auto_mesh
    return auto_mesh(tuple(cell.mesh_shape.values()),
                     tuple(cell.mesh_shape))


def make_engine(mesh):
    """The program's engine for a cell: ``JitEngine`` on one chip,
    ``ShardMapEngine`` over the cell's mesh."""
    from repro.core.engines import JitEngine, ShardMapEngine
    return JitEngine() if mesh is None else ShardMapEngine(mesh)


def memory_peak(devices):
    """(the fullest device's peak bytes in use, each device's), None
    where a device does not say."""
    each = [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in devices]
    return max((b for b in each if b is not None), default=None), each


def feed(cell: Cell):
    """The module of the traffic's feed of chunks: ``bench/feeds/``."""
    name = cell.traffic.get("feed", "device")
    return load_module(BENCH / "feeds" / f"{name}.py")


def request_rows(cell: Cell, key, n: int):
    """``n`` predict rows from the configuration's stream, drawn from the
    run's key apart from its chunks."""
    import jax
    gen = cell.family.stream(cell.cfg)

    @jax.jit
    def bench_request_rows(key):
        return gen.sample_binned(jax.random.fold_in(key, 1 << 30), n,
                                 cell.cfg["n_bins"])[0]

    return bench_request_rows(key)


class GcClock:
    """Seconds Python's garbage collector ran while ``on``, in all and in
    its longest pass: a collector's pause inside the window shows here."""

    def __init__(self):
        self.on = False
        self.seconds = 0.0
        self.longest = 0.0
        self._t = None
        gc.callbacks.append(self._cb)

    def _cb(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            d = time.perf_counter() - self._t
            self._t = None
            if self.on:
                self.seconds += d
                self.longest = max(self.longest, d)

    def close(self):
        gc.callbacks.remove(self._cb)


class PauseWatch:
    """A thread that sleeps ``tick`` seconds at a time and records by how
    much it overslept.  A pause of the whole host process (the machine, or
    a thread holding the interpreter) shows as an oversleep; a pause of
    the device, which the host only waits on, does not."""

    def __init__(self, tick: float = 0.05):
        import threading
        self.tick = tick
        self.longest = 0.0
        self.at = None              # seconds into the window
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="bench-pause-watch")

    def start(self):
        self.t0 = time.perf_counter()
        self._thread.start()

    def _run(self):
        while not self._stop.is_set():
            t = time.perf_counter()
            time.sleep(self.tick)
            over = time.perf_counter() - t - self.tick
            if over > self.longest:
                self.longest, self.at = over, t - self.t0

    def stop(self):
        self._stop.set()
        self._thread.join()


class RunInfo:
    """What a run measured; the metric readers take their numbers here."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def peaks(kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


def _short_op(text: str) -> str:
    """``%copy.100 = f32[...] copy(...)`` -> ``copy.100``."""
    return text.split(" = ")[0].lstrip("%")


def breakdown(dev: dict, n: int = 10) -> dict:
    ops = sorted(dev["op_self_time"].items(), key=lambda kv: -kv[1])[:n]
    return {"device_ops": [[_short_op(k), v] for k, v in ops],
            "idle_gaps": [[lab, g] for lab, g in
                          zip(dev["gap_labels"], dev["gaps"])][:n]}


def host_shards(x):
    """``(index, host copy)`` of each distinct part of ``x``: a device
    array's addressable shards, one of each group of replicas; a host
    array is one part, itself."""
    import jax
    if isinstance(x, jax.Array):
        for s in x.addressable_shards:
            if s.replica_id == 0:
                yield s.index, np.asarray(s.data)
    else:
        x = np.asarray(x)
        yield (slice(None),) * x.ndim, x


def compare(fam, prog: dict, ref: dict) -> tuple[int, float]:
    """(elements that differ among the exactly compared state arrays,
    largest relative gap among the others).  ``prog`` is the program's
    state on the host; ``ref`` the reference's, compared part by part
    (``host_shards``), so the host holds one shard of it at a time."""
    mismatch = 0
    for k in fam.EXACT:
        a = np.asarray(prog[k])
        if a.shape != ref[k].shape:
            mismatch += a.size
            continue
        for idx, b in host_shards(ref[k]):
            mismatch += int(np.count_nonzero(a[idx] != b))
    scales = [max(float(np.max(np.abs(b))) for _, b in host_shards(ref[k]))
              for k in fam.FLOAT]
    floor = float(np.median(scales)) if scales else 1.0
    gap = 0.0
    for k, scale in zip(fam.FLOAT, scales):
        a = np.asarray(prog[k])
        if a.shape != ref[k].shape:
            return mismatch, 1e30
        d = 0.0
        for idx, b in host_shards(ref[k]):
            part = np.asarray(a[idx], np.float64)
            if not np.all(np.isfinite(part)):
                return mismatch, 1e30  # not finite: no gap to measure
            if part.size:
                d = max(d, float(np.max(np.abs(
                    part - np.asarray(b, np.float64)))))
        gap = max(gap, d / max(scale, floor, 1e-30))
    return mismatch, gap


def replay(fam, cfg: dict, chunk, n_chunks: int, served, rows, mesh=None):
    """The reference over chunks 0..n_chunks-1: (its final state in the
    program's layout, left on the device, its prequential metric summed
    chunk by chunk in the order the program's is, the served answers that
    differ from its prediction at the chunk whose snapshot answered
    them).  On a mesh the reference places its state over it."""
    import jax.numpy as jnp
    s = fam.ref_init(cfg) if mesh is None else fam.ref_init(cfg, mesh=mesh)
    per_chunk = []
    by_chunk = defaultdict(list)
    for c, i, p in served:
        by_chunk[c].append((i, p))
    wrong = sum(len(v) for c, v in by_chunk.items() if c >= n_chunks)
    for c in range(n_chunks):
        pl = chunk(c)
        s, per = fam.ref_chunk(s, pl["x"], pl["y"], cfg)
        per_chunk.append(per)
        if by_chunk.get(c):
            idx = np.asarray([i for i, _ in by_chunk[c]])
            # padded to a multiple of 64 rows: few shapes to compile
            pad = np.resize(idx, -(-len(idx) // 64) * 64)
            want = np.asarray(fam.ref_predict(
                s, jnp.asarray(rows[pad]), cfg))[:len(idx)]
            got = np.asarray([p for _, p in by_chunk[c]])
            wrong += int(np.count_nonzero(got != want))
    total = sum(np.asarray(p, np.float64).sum() for p in per_chunk)
    return fam.ref_view(s), total, wrong


def run(cell: Cell, seed: int, seconds: float, trace: bool, *,
        t_start: float, window_chunks: int | None = None,
        check_device: bool = True, keep_trace: Path | None = None,
        log=print) -> dict:
    """One run; returns the result line's object (``checks`` last)."""
    import jax
    from jax.profiler import TraceAnnotation

    enable_compile_cache()
    if check_device:
        require_chips(cell.chips)
    sys.path.insert(0, str(ROOT / "src"))
    from repro.core.evaluation import ChunkedPrequentialEvaluation
    from repro.data.pipeline import ChunkedStream
    from repro.serving.snapshot import model_state_of

    from bench.load import OpenLoop, arrivals

    fam, cfg, tr = cell.family, cell.cfg, cell.traffic
    L = cfg["chunk_len"]
    key = seed_key(seed)
    counter = CompileCounter()
    mesh = make_mesh(cell)
    draw = feed(cell).chunk_fn(cell, key, mesh)
    drawn_at = []                   # host clock at each chunk's draw

    def chunk(i):
        drawn_at.append(time.perf_counter())
        with TraceAnnotation("bench.stream_gen"):
            return draw(i)

    learner = fam.learner(cfg)
    engine = make_engine(mesh)
    predict = tr.get("predict")
    publisher = server = load = None
    if tr.get("publish") or predict:
        from repro.serving import SnapshotPublisher
        publisher = SnapshotPublisher()

    def evaluate(n):
        """A prequential run over chunks 0..n-1 from a fresh state."""
        stream = ChunkedStream.from_fn(chunk, n, L)
        return ChunkedPrequentialEvaluation(
            learner, stream, engine=engine, publisher=publisher,
            key=jax.random.PRNGKey(0)).run(resume=False)

    # ---- set-up: this cell's shapes only
    span = min(seconds, cfg["trace_seconds"]) if trace else seconds
    warm = int(tr["warmup_chunks"])
    with TraceAnnotation("bench.warmup"):
        evaluate(warm)
    if predict:
        from repro.serving import ModelServer, ServeConfig
        offsets = arrivals(predict, span, seed)
        rows = np.asarray(request_rows(cell, key, max(len(offsets), 1)))
        server = ModelServer(learner, publisher, ServeConfig(
            max_batch=predict["max_batch"], max_wait_ms=predict["max_wait_ms"],
            queue_limit=predict["queue_limit"],
            deadline_ms=predict["deadline_ms"]))
        first = server.submit(rows[0]).result(timeout=600)
        if first.status != "answered":
            raise RuntimeError(f"warm-up request {first.status}")
        load = OpenLoop(server.submit, rows, offsets, predict["deadline_ms"])
    # a fixed amount of work: the chunks the configuration ran per second
    # on one v5e chip when the benchmark was defined
    n_chunks = window_chunks or max(warm, round(span * cfg["chunks_per_s"]))
    lead_in = int(predict.get("lead_in_chunks", 0)) if predict else 0
    n_chunks += lead_in
    gc.collect()
    gc_clock = GcClock()

    # ---- the window
    trace_dir = CACHE / "trace"
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    server0 = server.status() if server is not None else None
    compiles0 = counter.count()
    setup_s = time.time() - t_start
    del drawn_at[:]
    gc_clock.on = True
    pauses = PauseWatch()
    pauses.start()
    t0 = time.perf_counter()
    if load is not None:
        # the window's chunk indices restart at 0; the cursor the publisher
        # keeps from set-up is below the lead-in
        load.start((lambda: publisher.train_cursor >= lead_in - 1)
                   if lead_in else None)
    with TraceAnnotation("bench.window"):
        res = evaluate(n_chunks)
    window_s = time.perf_counter() - t0
    pauses.stop()
    gc_clock.on = False
    gc_clock.close()
    # the longest wait between two chunks' draws, the pipeline's pace: a
    # pause of the process or the device inside the window shows here
    draw_gaps = np.diff(np.asarray(drawn_at))
    if load is not None:
        load.join(timeout=span + 60)
        load.wait_answers(60.0)
    window_compiles = counter.count() - compiles0
    tsum = None
    if trace:
        jax.profiler.stop_trace()
        from bench import trace as tracing
        tsum = tracing.load(str(trace_dir))
        if keep_trace is not None:
            shutil.copy(tracing.find_xplane(str(trace_dir)), keep_trace)
        shutil.rmtree(trace_dir, ignore_errors=True)

    dev0 = jax.devices()[0]
    mem, chip_peaks = memory_peak(jax.devices()[:cell.chips])
    seen = float(res.extra["seen"])
    prog_metric = float(res.metric)
    prog = {k: np.asarray(v) for k, v in
            fam.program_state(model_state_of(res.extra["carry"])).items()}
    served = []
    status = None
    late = lat = None
    if load is not None:
        status = server.status()
        server.stop(drain=False)
        lat = load.latencies_ms()
        late = load.late_s * 1e3
        for i, r in enumerate(load.requests):
            if r is not None and r.status == "answered":
                served.append((int(r.meta["snapshot_chunk"]), i, r.pred))
    del res
    publisher = server = None
    gc.collect()

    # ---- the plain reference, on the same stream
    t_ref = time.perf_counter()
    with TraceAnnotation("bench.reference"):
        ref, ref_total, served_wrong = replay(
            fam, cfg, chunk, n_chunks, served, rows if predict else None,
            mesh)
        jax.block_until_ready(ref)
    ref_s = time.perf_counter() - t_ref

    t_cmp = time.perf_counter()
    mismatch, float_gap = compare(fam, prog, ref)
    compare_s = time.perf_counter() - t_cmp
    del ref
    mname, mgap = fam.metric_gap(prog_metric, ref_total, seen)
    numbers = {"state_mismatch": mismatch, mname: mgap}
    if fam.FLOAT:
        numbers["float_gap"] = float_gap
    if predict:
        numbers["served_wrong"] = served_wrong
    limits = cfg["limits"]
    checks = {}
    for name, value in numbers.items():
        if name not in limits:
            raise KeyError(f"configuration has no limit for {name!r}")
        checks[name] = {"value": value, "limit": limits[name]}
    correct = all(v["value"] <= v["limit"] for v in checks.values())

    n_req = len(load.offsets) if load is not None else 0
    unanswered = int(np.count_nonzero(~np.isfinite(lat))) if n_req else 0
    info = RunInfo(
        cfg=cfg, setup_s=setup_s, window_s=window_s, instances=seen,
        steps=n_chunks * L, window_compiles=window_compiles, trace=tsum,
        server=status, server0=server0, latencies_ms=lat, late_ms=late,
        work=cell.work(), chips=cell.chips, mesh=cell.mesh_shape or None,
        peaks=peaks(dev0.device_kind) if check_device else None)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = load_module(BENCH / "metrics" / f"{m['name']}.py").read(info)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": mem}
    out = {"correct": bool(correct), "attempted": int(seen) + n_req,
           "failed": unanswered, "metrics": metrics, "device": device}
    if tsum is not None:
        devs = tsum["devices"][:cell.chips]
        device["busy_s"] = sum(d["busy_s"] for d in devs) / max(len(devs), 1)
        device["window_s"] = tsum["window_s"]
        if devs:
            out["breakdown"] = breakdown(devs[0])
    log(f"window: {n_chunks} chunks, {seen:.0f} instances, "
        f"{window_s:.3f} s; reference {ref_s:.3f} s; compare "
        f"{compare_s:.3f} s; setup {setup_s:.3f} s")
    out["report"] = {
        "window_chunks": n_chunks, "window_s": window_s,
        "reference_s": ref_s, "compare_s": compare_s, "server": status,
        "longest_draw_gap_s": float(draw_gaps.max()) if draw_gaps.size
        else None,
        "longest_draw_gap_at": int(draw_gaps.argmax()) + 1
        if draw_gaps.size else None,
        "gc_s": gc_clock.seconds, "longest_gc_s": gc_clock.longest,
        "longest_host_pause_s": pauses.longest,
        "longest_host_pause_at_s": pauses.at}
    if cell.chips > 1:
        out["report"]["memory_peak_bytes_per_chip"] = chip_peaks
    if n_req:
        half = len(lat) // 2
        p95 = lambda v: float(np.percentile(v, 95, method="higher"))
        out["report"]["latency_ms"] = {
            "p50": float(np.percentile(lat, 50, method="higher")),
            "p95": p95(lat), "p95_first_half": p95(lat[:half]),
            "p95_second_half": p95(lat[half:]), "requests": n_req}
    out["checks"] = checks
    return out
