"""The benchmark harness: one cell, one seed, one process.

A cell (``BENCHMARK.json``'s ``workloads``) names a configuration file
(``bench/configs/<config>.json``, with its reference module beside it), a
traffic mix (``bench/traffic/<mix>.json``) and, through the manifest, the
per-layer metrics it reports (``bench/metrics/<metric>.py``).  Nothing
here names a cell, a configuration or a metric: adding one is adding files
and manifest entries.

Set-up makes the weights on the device from the seed (one jitted call in
the configuration's dtype), builds the engine with
``repro.launch.serve.build_engine``, warms its programs, and serves the
cell's traffic for the mix's ``ramp_s``.  The window then drives
``ServeEngine.submit`` / ``ServeEngine.step`` from this module's own loop
and stamps every output token on the host clock.  After the window the
loop keeps stepping, without new requests, until every request due in it
has its first token (or ``DRAIN_S`` has passed); then a sample of the
finished requests is compared with the configuration's float32
reference (``compare``).
"""
from __future__ import annotations

import bisect
import gc
import importlib.util
import json
import sys
import time
from contextlib import nullcontext
from dataclasses import dataclass, field
from pathlib import Path


BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent
RUNS = BENCH / ".runs"          # traces of --trace 1 runs (not committed)
DRAIN_S = 60.0                  # how long requests may finish after close
TRACER_EVENTS = 1 << 19         # the engine tracer's ring, far above a run
#: the host spans this harness adds to a traced run, and the engine's
HOST_SPANS = ("generate", "submit", "step", "wait_arrival",
              "prefill", "prefill_chunk", "decode", "kv_copy")


def src_on_path() -> None:
    """The system under test is the repository's ``src`` package."""
    src = str(REPO / "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def load_module(path: Path):
    """Import a Python file of the benchmark by its path."""
    path = Path(path).resolve()
    name = "bench_file_" + "".join(c if c.isalnum() else "_"
                                   for c in str(path))
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


# ------------------------------------------------------------------ cells
@dataclass
class Cell:
    name: str
    workload: dict
    config: dict
    family: object              # the configuration's reference module
    mix: dict
    end_to_end: list            # manifest entries this cell reports
    per_layer: list
    readers: dict               # per-layer metric name -> reader module


def load_manifest(root: Path = REPO) -> dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def _reports(metric: dict, cell: str, e2e_of_cell: set) -> bool:
    """Whether a cell reports a metric: the cells its ``workloads`` key
    lists, or, without one, every cell (an end-to-end metric) or every
    cell that reports the metric it ``moves`` (a per-layer metric)."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return "moves" not in metric or metric["moves"] in e2e_of_cell


def cell(manifest: dict, name: str, root: Path = REPO) -> Cell:
    """Resolve a cell's configuration, mix and metric readers by name."""
    root = Path(root)
    work = {w["name"]: w for w in manifest["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r}; have {sorted(work)}")
    w = work[name]
    conf = {c["name"]: c for c in manifest["configs"]}[w["config"]]
    cfg_path = root / conf["file"]
    cfg = json.loads(cfg_path.read_text())
    family = load_module(cfg_path.parent / f"{cfg['family']}.py")
    bench = cfg_path.parent.parent
    mix = json.loads((bench / "traffic" / f"{w['traffic']}.json").read_text())
    e2e = [m for m in manifest["end_to_end"] if _reports(m, name, set())]
    names = {m["name"] for m in e2e}
    pl = [m for m in manifest["per_layer"] if _reports(m, name, names)]
    readers = {m["name"]: load_module(bench / "metrics" / f"{m['name']}.py")
               for m in pl}
    return Cell(name, w, cfg, family, mix, e2e, pl, readers)


# -------------------------------------------------------------- records
@dataclass
class Rec:
    """One request as the client sees it."""
    spec: object                # traffic.Spec
    due: float
    req: object = None          # the engine's Request
    submit: float | None = None
    admit: float | None = None
    prefix_hit: int = 0
    stamps: list = field(default_factory=list)
    done: float | None = None

    @property
    def prompt_len(self) -> int:
        return len(self.spec.prompt)


@dataclass
class Tick:
    t0: float
    t1: float
    decode_ctx: list = field(default_factory=list)  # per decoded token
    prefills: list = field(default_factory=list)    # (rows, bucket, lens)
    chunks: list = field(default_factory=list)      # (offset, n, final)
    traced: bool = False


@dataclass
class Run:
    """What a run leaves for the metric readers."""
    cell: Cell
    open: float
    close: float
    records: list
    ticks: list
    counters: dict              # engine counters over the window
    engine: dict                # block size, prefill rows, largest bucket
    peaks: dict | None
    trace: object = None        # trace_reduce.Trace of the traced ticks
    trace_lo: float = 0.0       # traced window on the trace's clock (ns)
    trace_hi: float = 0.0
    drained: float = 0.0        # when the drain after the window ended
    info: dict = field(default_factory=dict)    # what ``drive`` reports

    def window_ticks(self) -> list:
        return [t for t in self.ticks if self.open <= t.t0 and t.t1 <= self.close]

    def traced_ticks(self) -> list:
        return [t for t in self.ticks if t.traced]


# ----------------------------------------------------------------- set-up
def arch_config(cfg: dict):
    src_on_path()
    from repro.configs import get_config
    return get_config(cfg["arch"]).replace(**cfg["overrides"])


def seed_key(seed: int):
    """A PRNG key from any whole number: its low and high 31 bits are
    folded in, so seeds past 32 bits differ too."""
    import jax
    k = jax.random.PRNGKey(0)
    k = jax.random.fold_in(k, seed & 0x7FFFFFFF)
    return jax.random.fold_in(k, (seed >> 31) & 0x7FFFFFFF)


def make_weights(c: Cell, seed: int):
    """The weights on the device from the seed, in one jitted call, checked
    against the layout the program's model takes."""
    import jax
    import jax.numpy as jnp
    src_on_path()
    from repro.models import build_model
    arch = arch_config(c.config)
    dtype = jnp.dtype(arch.param_dtype)
    pub = c.config["published"]
    init = jax.jit(lambda k: c.family.init(pub, k, dtype))
    want = jax.eval_shape(build_model(arch).init, jax.random.PRNGKey(0))
    got = jax.eval_shape(init, seed_key(seed))
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise ValueError(f"{c.config['name']}: the reference's weights do not "
                         f"have the program's layout")
    params = init(seed_key(seed))
    jax.block_until_ready(params)
    return params


def make_engine(c: Cell, params, *, profile: bool = False):
    src_on_path()
    from repro.launch.serve import build_engine
    from repro.obs import Tracer
    e = dict(c.config["engine"])
    eng = build_engine(arch_config(c.config), params, **e)
    eng.tracer = Tracer(capacity=TRACER_EVENTS, clock=time.perf_counter)
    eng.profile = profile
    return eng


# -------------------------------------------------------------- the loop
class _Ann:
    """TraceAnnotation when tracing, nothing otherwise."""

    def __init__(self, on: bool):
        if on:
            import jax
            self._make = jax.profiler.TraceAnnotation
        else:
            self._make = None

    def __call__(self, name: str):
        return self._make(name) if self._make else nullcontext()


_COUNTERS = ("prefill_tokens_computed", "prefill_time_s", "decode_time_s",
             "decode_steps")


def _counters(engine) -> dict:
    return {k: getattr(engine.stats, k) for k in _COUNTERS}


def drive(engine, specs, *, start: float, ramp: float, seconds: float,
          backlog: int = 0, trace_dir: Path | None = None,
          trace_span: tuple[float, float] | None = None,
          drain_s: float = DRAIN_S,
          clock=time.perf_counter) -> tuple[list, list, dict, dict]:
    """Serve ``specs`` from ``start`` for ``ramp`` + ``seconds`` seconds,
    then drain.  Open loop: a spec is due at ``start + spec.due``.
    ``backlog`` > 0: the specs are offered in order whenever fewer than
    ``backlog`` requests are unfinished, due when offered.  After the
    window no request is offered, and the loop steps until every request
    due in the window has its first token, for at most ``drain_s``; what
    is still running then is left.  With ``trace_dir`` the profiler
    records ``trace_span`` (two times on ``clock`` inside the window),
    without Python's own calls.  Returns (records, ticks, counters over
    the window, {"traced": (first, last) traced tick index,
    "window_compiles": programs compiled inside the window, "drained":
    when the drain ended})."""
    src_on_path()
    import jax
    from repro.serve.engine import Request
    ann = _Ann(trace_dir is not None)
    compiles = [0]
    in_window = [False]

    def on_compile(event, _secs, **_kw):
        if in_window[0] and event == "/jax/core/compile/backend_compile_duration":
            compiles[0] += 1

    jax.monitoring.register_event_duration_secs_listener(on_compile)
    open_, close = start + ramp, start + ramp + seconds
    recs: list[Rec] = []
    live: dict[int, Rec] = {}
    ticks: list[Tick] = []
    i, n = 0, len(specs)
    c_open = c_close = None
    tracing = False
    traced = [None, None]

    def offer(now: float) -> list:
        nonlocal i
        out = []
        if backlog:
            while i < n and len(live) + len(out) < backlog:
                out.append(Rec(specs[i], now))
                i += 1
        else:
            while i < n and start + specs[i].due <= now:
                out.append(Rec(specs[i], start + specs[i].due))
                i += 1
        return out

    def tick() -> None:
        t0 = clock()
        with ann("step"):
            engine.step()
        t1 = clock()
        tk = Tick(t0, t1, traced=tracing)
        with ann("generate"):
            for rid, r in list(live.items()):
                got = len(r.req.generated)
                for k in range(len(r.stamps), got):
                    if k == 0:
                        r.stamps.append(r.req.t_first_token)
                    else:
                        r.stamps.append(t1)
                        tk.decode_ctx.append(r.prompt_len + k)
                if r.req.done:
                    r.done = r.req.t_done
                    del live[rid]
        ticks.append(tk)

    while True:
        now = clock()
        if c_open is None and now >= open_:
            c_open = _counters(engine)
            in_window[0] = True
        if now >= close:
            break
        if trace_dir is not None and traced[0] is None \
                and now >= trace_span[0]:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
            tracing = True
            traced[0] = len(ticks)
        if tracing and now >= trace_span[1]:
            traced[1] = len(ticks)
            jax.profiler.stop_trace()
            tracing = False
        with ann("generate"):
            new = offer(now)
        if new:
            with ann("submit"):
                for r in new:
                    sp = r.spec
                    r.req = Request(rid=sp.idx, prompt=sp.prompt.tolist(),
                                    max_new_tokens=sp.max_new)
                    engine.submit(r.req)
                    r.submit = r.req.t_submit
                    live[sp.idx] = r
                    recs.append(r)
        if live:
            tick()
        else:
            nxt = close if (backlog or i >= n) else start + specs[i].due
            with ann("wait_arrival"):
                time.sleep(max(0.0, min(nxt, close) - clock()))
    c_close = _counters(engine)
    in_window[0] = False
    jax.monitoring.unregister_event_duration_listener(on_compile)
    if c_open is None:
        c_open = c_close
    if tracing:
        traced[1] = len(ticks)
        jax.profiler.stop_trace()
        tracing = False
    # drain: no new requests; step until each request due in the window
    # has its first token
    end = clock() + drain_s
    waiting = [r for r in live.values() if r.due < close]
    while clock() < end and any(not r.stamps for r in waiting):
        tick()
    drained = clock()
    _read_tracer(engine, recs, ticks, since=start)
    counters = {k: c_close[k] - c_open[k] for k in _COUNTERS}
    return recs, ticks, counters, {"traced": tuple(traced),
                                   "window_compiles": compiles[0],
                                   "drained": drained}


def _read_tracer(engine, recs: list, ticks: list, since: float) -> None:
    """Admission times, prefix hits, and the prefill and chunk calls of
    each tick, from the engine's tracer events since ``since``."""
    tr = engine.tracer
    if tr.dropped:
        raise RuntimeError(f"the engine's tracer dropped {tr.dropped} events")
    by_rid = {r.spec.idx: r for r in recs}
    starts = [t.t0 for t in ticks]
    calls: dict[tuple, list] = {}
    for ph, name, _tid, ts, dur, args in tr.events():
        if ts < since:
            continue
        a = dict(args)
        if ph == "B" and name.startswith("req ") and a.get("rid") in by_rid:
            r = by_rid[a["rid"]]
            r.admit = ts
            r.prefix_hit = int(a.get("prefix_hit_tokens", 0))
            continue
        if ph != "X" or name not in ("prefill", "prefill_chunk"):
            continue
        k = bisect.bisect_right(starts, ts) - 1
        if k < 0 or ts > ticks[k].t1:
            continue
        if name == "prefill_chunk":
            off, n = int(a["offset"]), int(a["n"])
            r = by_rid.get(a["rid"])
            final = r is not None and off + n >= r.prompt_len
            ticks[k].chunks.append((off, n, final))
        else:
            calls.setdefault((k, ts), []).append(a)
    for (k, _ts), rows in sorted(calls.items()):
        lens = [by_rid[a["rid"]].prompt_len for a in rows if a["rid"] in by_rid]
        ticks[k].prefills.append((len(rows), int(rows[0]["bucket"]), lens))


# ------------------------------------------------------------- end to end
def end_to_end(run: Run, setup_s: float) -> dict:
    """The cell's end-to-end metrics, host clock."""
    from . import stats
    out = {}
    for m in run.cell.end_to_end:
        name = m["name"]
        if name == "setup_s":
            v = setup_s
        elif name == "ttft_p95_ms":
            v = stats.percentile(stats.ttft_tail(
                run.records, run.open, run.close, run.drained), 95)
            v = None if v is None else 1e3 * v
        elif name == "tbt_p99_ms":
            v = stats.percentile(
                stats.token_gaps(run.records, run.open, run.close), 99)
            v = None if v is None else 1e3 * v
        elif name == "tokens_per_s":
            v = stats.tokens_in(run.records, run.open, run.close) \
                / (run.close - run.open)
        else:
            raise KeyError(f"no arithmetic for end-to-end metric {name!r}")
        if v is not None:
            out[name] = {"value": v, "unit": m["unit"]}
    return out


def per_layer(run: Run) -> dict:
    out = {}
    for m in run.cell.per_layer:
        v = run.cell.readers[m["name"]].read(run)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def attempted_failed(run: Run) -> tuple[int, int]:
    """Requests due in the window, and those of them that got no first
    token before the drain ended."""
    due = [r for r in run.records if run.open <= r.due < run.close]
    return len(due), sum(1 for r in due if not r.stamps)


def release(engine) -> None:
    """Free the engine's device state (the weights stay with the caller)
    and let the collector walk what set-up froze."""
    engine.states = None
    engine.params = None
    gc.unfreeze()
    gc.collect()


# ------------------------------------------------------------------ a run
TRACE_S = 4.0                   # the traced part of a --trace 1 window
MIN_COMPARED = 100              # served tokens the comparison must read


def batch_rows(engine):
    """The padded row count of a prefill call of ``n`` real rows."""
    buckets = tuple(engine.batch_buckets)
    return lambda n: next(b for b in buckets if n <= b)


def map_trace(run: Run, path, first_last: tuple) -> None:
    """Read the trace and find the traced ticks on its clock: the k-th
    ``step`` span of the trace is the k-th traced tick."""
    from . import trace_reduce
    tr = trace_reduce.load(path, host_names=set(HOST_SPANS))
    steps = sorted(tr.spans("step"))
    m = first_last[1] - first_last[0]
    if m <= 0 or len(steps) < m:
        raise RuntimeError(f"the trace holds {len(steps)} step spans for "
                           f"{m} traced ticks")
    run.trace, run.trace_lo, run.trace_hi = tr, steps[0][0], steps[m - 1][1]


def setup(c: Cell, seed: int, *, profile: bool = False,
          log=print) -> tuple[object, object, dict]:
    """The weights from the seed, the cell's engine with its programs
    warmed.  Returns (weights, engine, the seconds of each part)."""
    parts = {}
    t = time.perf_counter()
    params = make_weights(c, seed)
    parts["init_s"] = time.perf_counter() - t
    t = time.perf_counter()
    engine = make_engine(c, params, profile=profile)
    parts["engine_s"] = time.perf_counter() - t
    t = time.perf_counter()
    engine.warmup()
    # what set-up left on the heap (compiler objects, most of all after a
    # cold compile) lives until ``release``; keep the collector's passes
    # in the window from walking it
    gc.collect()
    gc.freeze()
    parts["warmup_s"] = time.perf_counter() - t
    log(f"engine: buckets {list(engine.buckets)}, batch buckets "
        f"{list(engine.batch_buckets)}, prefill chunk {engine.prefill_chunk}, "
        f"plan {json.dumps(engine.policy.summary().get('variants', []))}")
    return params, engine, parts


def serve(c: Cell, engine, specs, seconds: float, *,
          trace_dir: Path | None = None, drain_s: float | None = None) -> Run:
    """The cell's ramp, window and drain on a warm engine: the one path
    that a run, the rate sweep and the limit readings all time.  With
    ``trace_dir`` the profiler records ``TRACE_S`` seconds from the mix's
    ``trace_at_s`` (the window's end by default)."""
    mix, slots = c.mix, c.config["engine"]["slots"]
    backlog = mix.get("backlog_per_slot", 0) * slots + slots \
        if mix["kind"] == "backlog" else 0
    start = time.perf_counter()
    open_ = start + mix["ramp_s"]
    span = None
    if trace_dir is not None:
        length = min(TRACE_S, seconds / 2)
        at = min(max(mix.get("trace_at_s", seconds), 0.0), seconds - length)
        span = (open_ + at, open_ + at + length)
    recs, ticks, counters, info = drive(
        engine, specs, start=start, ramp=mix["ramp_s"], seconds=seconds,
        backlog=backlog, trace_dir=trace_dir, trace_span=span,
        drain_s=DRAIN_S if drain_s is None else drain_s)
    return Run(c, open_, open_ + seconds, recs, ticks, counters,
               {"kv_block_size": c.config["engine"].get("kv_block_size"),
                "batch_rows": batch_rows(engine),
                "max_bucket": engine.buckets[-1]}, None,
               drained=info["drained"], info=info)


def judge(ref, run: Run, params, seed: int) -> tuple[dict, list]:
    """The comparison of ``compare`` on the sample drawn from the seed
    (after ``release``): its numbers, and the sample."""
    from . import compare
    sample = compare.pick_sample(run.records, seed, run.cell.config["correct"],
                                 run.engine["max_bucket"])
    return compare.compare(ref, params, sample), sample


def run_cell(c: Cell, seed: int, seconds: float, *, trace: bool,
             peaks: dict | None, t_proc: float, device=None,
             compile_cache: bool = True, log=print,
             runs_dir: Path = RUNS) -> tuple[dict, list]:
    """Set up, serve the window, drain, compare.  Returns the result line
    (as a dict) and the checks ``(name, value, rule, limit)``."""
    import jax
    from . import compare, trace_reduce, traffic
    src_on_path()
    cache = None
    if compile_cache:
        from repro.launch.compile_cache import setup_compile_cache
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        cache = setup_compile_cache()
    specs = traffic.generate(c.mix, seed, seconds=seconds,
                             vocab=arch_config(c.config).vocab_size)
    params, engine, parts = setup(c, seed, profile=trace, log=log)
    tdir = None
    if trace:
        import shutil
        tdir = Path(runs_dir) / f"trace-{c.name}"
        shutil.rmtree(tdir, ignore_errors=True)
        tdir.mkdir(parents=True)
    run = serve(c, engine, specs, seconds, trace_dir=tdir)
    run.peaks = peaks
    parts["ramp_s"] = c.mix["ramp_s"]
    setup_s = run.open - t_proc
    if cache is not None:
        parts["compile_cache_hits"] = cache.hits
        parts["compile_cache_writes"] = cache.writes
    log("setup parts: " + json.dumps(parts))
    recs = run.records
    log(f"window: {run.info['window_compiles']} compiles; "
        f"{sum(1 for r in recs if r.done is not None)} of {len(recs)} "
        f"requests finished")
    dev = device or jax.devices()[0]
    mem = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
    dev_out = {"platform": dev.platform, "kind": dev.device_kind,
               "count": c.workload["chips"], "memory_peak_bytes": int(mem)}
    out = {}
    if trace:
        map_trace(run, trace_reduce.find_xplane(tdir), run.info["traced"])
        lo, hi = run.trace_lo, run.trace_hi
        tt = run.traced_ticks()
        log(f"traced: {len(tt)} ticks from "
            f"{(tt[0].t0 if tt else run.close) - run.open:.1f} s after the "
            f"window opened; "
            f"{sum(len(t.prefills) for t in tt)} prefill calls, "
            f"{sum(len(t.chunks) for t in tt)} chunk calls")
        dev_out["busy_s"] = trace_reduce.busy_ns(run.trace, lo, hi) * 1e-9
        dev_out["window_s"] = (hi - lo) * 1e-9
        metrics = per_layer(run)
        out["breakdown"] = {
            "device_ops": [list(x) for x in
                           trace_reduce.top_ops(run.trace, lo, hi)],
            "idle_gaps": [list(x) for x in
                          trace_reduce.idle_gaps(run.trace, lo, hi)]}
    else:
        metrics = end_to_end(run, setup_s)
    attempted, failed = attempted_failed(run)
    release(engine)
    del engine
    t = time.perf_counter()
    got, _ = judge(compare.Reference(c.family, c.config["published"]), run,
                   params, seed)
    log(f"reference: {got['requests']} requests, {got['tokens']} served "
        f"tokens, {time.perf_counter() - t:.1f} s")
    checks = [("logit_gap", got["logit_gap"], "<=",
               c.config["correct"]["max_logit_gap"]),
              ("tokens_compared", got["tokens"], ">=", MIN_COMPARED),
              ("unserved", failed, "<=", 0)]
    correct = all(lim is not None and (v <= lim if rule == "<=" else v >= lim)
                  for _n, v, rule, lim in checks)
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": dev_out}
    result.update(out)
    result["checks"] = {n: {"value": v, rule: lim}
                        for n, v, rule, lim in checks}
    return result, checks
