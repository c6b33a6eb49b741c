"""Run one cell once: set-up, the measured window, the check, the result.

Set-up makes the weights on the device from the seed, builds one
``ServeEngine`` as the configuration states, warms the prefill shapes the
mix's later requests need, fills the batch (one step per decode page bucket
it warms), steps to the first decode-path page seal and warms the page
pushes a step can make.  The window is whole ``ServeEngine.step()`` calls,
run until ``seconds`` have passed (``TRACE_SECONDS`` at most in a traced
run); every step syncs on the token ids, and the harness stamps each token
when its step returns.  The loop is closed: when a request is served its
last token, the next one of the list is submitted, and the engine admits
it in the next step.  Then the program's state is freed and ``check.py``
compares the served tokens with the reference.
"""
from __future__ import annotations

import dataclasses
import gc
import itertools
import shutil
import sys
import tempfile
import time

import numpy as np

import check
import spec
import traffic as traffic_gen
import weights as weight_gen

sys.path.insert(0, str(spec.ROOT / "src"))

GIB = 2 ** 30
TRACE_SECONDS = 10.0                   # window of a --trace 1 run, at most


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class CompileCounter:
    """Counts the programs built (XLA compiles and loads from the
    persistent cache alike) while armed, with their names."""

    def __init__(self):
        import jax
        self.armed = False
        self.names: list[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _duration(self, event: str, duration: float, **kw) -> None:
        if self.armed and event == "/jax/core/compile/backend_compile_duration":
            self.names.append(f"{kw.get('fun_name', '?')} {duration:.3f} s")

    @property
    def count(self) -> int:
        return len(self.names)


class Spans:
    """Harness-side spans around calls into the program's layers: each
    records (start, end) on ``perf_counter`` and, while a trace is taken,
    a host annotation of the same name."""

    def __init__(self):
        self.times: dict[str, list[tuple[float, float]]] = {}

    def wrap(self, obj, attr: str, name: str) -> None:
        import jax
        inner = getattr(obj, attr)
        times = self.times.setdefault(name, [])

        def timed(*args, **kwargs):
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation(f"bench.{name}"):
                out = inner(*args, **kwargs)
            times.append((t0, time.perf_counter()))
            return out

        setattr(obj, attr, timed)

    def within(self, name: str, t0: float, t1: float,
               outside: str | None = None) -> list[float]:
        """Durations of the ``name`` spans that start in [t0, t1), without
        those nested in an ``outside`` span."""
        skip = self.times.get(outside, []) if outside else []
        return [b - a for a, b in self.times.get(name, []) if t0 <= a < t1
                and not any(c <= a and b <= d for c, d in skip)]


def check_layout(cfg, params) -> None:
    """The tree the architecture's ``program_params`` builds has the
    program's own layout (shapes only are read from the program)."""
    import jax
    from repro.models import model as M
    want = jax.eval_shape(lambda: M.init_params(cfg, jax.random.PRNGKey(0)))
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                       params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype) for a, b in
            zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise RuntimeError("the program's parameter layout changed: "
                           f"{jax.tree.structure(want)}")


def build_engine(cfg, params, model: dict, mix: dict):
    from repro.serve import ServeEngine
    s = model["serving"]
    return ServeEngine(cfg, params, max_batch=int(mix["slots"]),
                       max_len=int(mix["max_len"]),
                       kv_page_size=int(s["page_size"]),
                       scheduler=s["scheduler"], weights=s["weights"])


@dataclasses.dataclass
class Served:
    spec: traffic_gen.Spec
    req: object                        # repro.serve.Request
    stamps: list                       # perf_counter at each token


class ClosedLoop:
    """Keeps every slot busy: submits the next request of ``requests`` as
    soon as one has been served its last token."""

    def __init__(self, eng, requests):
        self.eng = eng
        self.requests = requests
        self.served: dict[int, Served] = {}
        self.replaced: set[int] = set()

    def submit(self, s: traffic_gen.Spec) -> None:
        from repro.serve import Request
        req = Request(rid=s.rid, prompt=s.prompt, max_new_tokens=s.max_new)
        self.served[s.rid] = Served(s, req, [])
        self.eng.submit(req)

    def step(self) -> float:
        """One engine step; stamps its tokens and tops the queue up.
        Returns the time the step returned."""
        self.eng.step()
        t = time.perf_counter()
        for sv in list(self.served.values()):
            new = len(sv.req.tokens) - len(sv.stamps)
            if new > 0:
                sv.stamps.extend([t] * new)
            rid = sv.spec.rid
            if rid not in self.replaced and len(sv.req.tokens) >= sv.spec.max_new:
                self.replaced.add(rid)
                self.submit(next(self.requests))
        return t


def warm_prefill(eng, fill: list, later: list, max_len: int) -> int:
    """Compile and run once each prefill shape that a request of ``later``
    needs and the fill does not reach.  Returns the number warmed."""
    import jax
    from repro.serve.engine import prefill_bucket

    def key(n):
        b = prefill_bucket(n, max_len)
        return b, n == b

    have = {key(len(s.prompt)) for s in fill}
    todo = {}
    for s in later:
        todo.setdefault(key(len(s.prompt)), len(s.prompt))
    n = 0
    for k, length in sorted(todo.items()):
        if k not in have:
            jax.block_until_ready(eng._prefill_forward(
                np.zeros(length, np.int32)))
            n += 1
    return n


def fill_groups(fill: list, page_size: int, max_len: int) -> list[list]:
    """The fill in two admission groups, so that the decode programs of
    both of the two largest page-grid buckets the batch reaches compile in
    set-up: first every request up to the second-largest bucket, then the
    rest."""
    from repro.kernels.paged_decode import page_bucket
    cap = -(-max_len // page_size)

    def bucket(s):
        return min(page_bucket(-(-len(s.prompt) // page_size)), cap)

    bks = sorted({bucket(s) for s in fill})
    low = bks[-2] if len(bks) > 1 else bks[-1]
    first = [s for s in fill if bucket(s) <= low]
    rest = [s for s in fill if bucket(s) > low]
    return [g for g in (first, rest) if g]


def seal_in_a_step(loop: ClosedLoop, page_size: int) -> int:
    """Step until a step that admits nothing packs a page, so that the
    decode path's page seal (pull, encode, push) has run once in set-up.
    Returns the number of steps taken (at most ``page_size``)."""
    eng = loop.eng
    for n in range(1, page_size + 1):
        before = eng.kv_stats()["kv_pages_packed"]
        queued = len(eng.queue)
        loop.step()
        if queued == 0 and eng.kv_stats()["kv_pages_packed"] > before:
            return n
    return page_size


def warm_pushes(eng, slots: int) -> int:
    """Run once each page push that a decode step can make: a step seals
    the pages of 1 to ``slots`` sequences at once, one per layer each, and
    the engine pushes them to the device in one eager scatter per plane,
    compiled per page count.  Pushing pages that are already PACKED again
    writes what the device holds.  Returns the number of counts warmed."""
    from repro.models.modules import PAGE_PACKED
    kv = eng.kv
    layers = len(kv.attn_layers)
    packed = [p for tables in kv.page_tables.values() for t in tables
              for p in t if p >= 0 and kv.pool.state[p] == PAGE_PACKED]
    n = 0
    for m in range(1, slots + 1):
        if m * layers > len(packed):
            break
        kv.sync_pages_to_device(packed[:m * layers])
        n += 1
    return n


def run(cell: spec.Cell, seed: int, seconds: float, trace: bool,
        t_start: float, peaks: dict, device, control: str | None = None
        ) -> dict:
    """One run; the result line as a dict.  With ``control``
    (``control.py`` and the tests only) the reference in that lower
    precision stands in the program's place in the comparison that decides
    ``correct``, on the same prompts and served tokens."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache

    model, mix = cell.config, cell.traffic
    cache_dir = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    counter = CompileCounter()
    log(f"device {device.device_kind} x{len(jax.devices())}; compile cache "
        f"{cache_dir}")

    cfg = cell.arch.program_config(model)
    int8 = model["serving"]["weights"] == "apack-int8"
    t0 = time.perf_counter()
    params = cell.arch.program_params(weight_gen.make(model, seed, int8=int8))
    check_layout(cfg, params)
    jax.block_until_ready(params)
    t1 = time.perf_counter()
    eng = build_engine(cfg, params, model, mix)
    del params
    t2 = time.perf_counter()
    log(f"set-up: weights {t1 - t0:.2f} s, engine {t2 - t1:.2f} s")

    requests = traffic_gen.generate(mix, model["vocab_size"], seed)
    slots = int(mix["slots"])
    fill = list(itertools.islice(requests, slots))
    # every later block asks for the sizes of this one
    ahead = list(itertools.islice(requests, slots))
    loop = ClosedLoop(eng, itertools.chain(ahead, requests))
    n_warm = warm_prefill(eng, fill, ahead, int(mix["max_len"]))
    t3 = time.perf_counter()
    page_size = int(model["serving"]["page_size"])
    groups = fill_groups(fill, page_size, int(mix["max_len"]))
    for g in groups:
        for s in g:
            loop.submit(s)
        loop.step()
    n_seal = seal_in_a_step(loop, page_size)
    n_push = warm_pushes(eng, slots)
    t4 = time.perf_counter()
    log(f"set-up: {n_warm} prefill shapes warmed in {t3 - t2:.2f} s; fill "
        f"of {len(fill)} requests in {len(groups)} steps, {n_seal} more "
        f"steps to the first decode-path seal, {n_push} page-push shapes, "
        f"{t4 - t3:.2f} s")

    spans = Spans()
    tdir = None
    if trace:
        spans.wrap(eng, "_prefill_into_slot", "admit")
        spans.wrap(eng.kv, "_seal", "seal")
        spans.wrap(eng.kv, "_flush_device", "seal")
        tdir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0           # no Python frames
        opts.host_tracer_level = 1             # annotations, not every call
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(tdir, profiler_options=opts)
    kv0 = eng.kv_stats()
    counter.armed = True
    t_w0 = time.perf_counter()
    step_ends = []
    # a traced run measures a shorter window: the profiler's collection
    # grows with the device programs run, about 8 s for each second of
    # the chat cell's window, and a run has to end within its limit
    if trace:
        seconds = min(seconds, TRACE_SECONDS)
    while True:
        if trace:
            with jax.profiler.TraceAnnotation("bench.step"):
                t = loop.step()
        else:
            t = loop.step()
        step_ends.append(t)
        if t - t_w0 >= seconds:
            break
    counter.armed = False
    window_s = step_ends[-1] - t_w0
    if trace:
        t_stop = time.perf_counter()
        jax.profiler.stop_trace()
        log(f"trace: stop_trace {time.perf_counter() - t_stop:.2f} s")
    kv1 = eng.kv_stats()
    mem = device.memory_stats() or {}
    peak = int(mem.get("peak_bytes_in_use", 0))
    log(f"device memory after the window: {mem.get('bytes_in_use', 0)} B in "
        f"use, peak {peak} B")
    setup_s = t_w0 - t_start

    # tokens, gaps and the requests served in the window
    tokens = 0
    gaps = []
    in_window = []
    for sv in loop.served.values():
        st = sv.stamps
        k0 = next((i for i, t in enumerate(st) if t > t_w0), None)
        if k0 is None:
            continue
        in_window.append(sv)
        tokens += len(st) - k0
        gaps.extend(st[i] - max(st[i - 1], t_w0)
                    for i in range(max(k0, 1), len(st)))
    failed = sum(1 for sv in in_window if sv.req.error is not None)
    log(f"window: {len(step_ends)} steps, {window_s:.3f} s, {tokens} tokens, "
        f"{len(gaps)} gaps, {len(in_window)} requests, "
        f"{sum(1 for sv in in_window if sv.req.done)} finished; "
        f"pages packed {kv1['kv_pages_packed'] - kv0['kv_pages_packed']}; "
        f"step seconds {[round(b - a, 3) for a, b in zip([t_w0] + step_ends, step_ends)]}")
    print(f"compiles in window: {counter.count}", flush=True)
    if counter.names:
        log(f"compiled in the window: {counter.names}")

    metrics = {}
    if not trace:
        values = {"tokens_per_s": tokens / window_s,
                  "token_gap_p95_ms": float(np.percentile(gaps, 95)) * 1e3,
                  "peak_hbm_gib": peak / GIB, "setup_s": setup_s}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    reduced = None
    if trace:
        import xplane_reduce
        t_red = time.perf_counter()
        reduced = xplane_reduce.reduce_dir(tdir)
        shutil.rmtree(tdir, ignore_errors=True)
        log(f"trace: reduced in {time.perf_counter() - t_red:.2f} s")
        if reduced is None:
            raise RuntimeError("the trace holds no step or no device")
        ctx = MetricContext(model=model, peaks=peaks, trace=reduced,
                            spans=spans, window=(t_w0, step_ends[-1]),
                            step_ends=step_ends, served=in_window, kv0=kv0,
                            kv1=kv1, weight_stats=eng.weight_stats())
        for m in cell.per_layer:
            v = spec.load_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        for line in ctx.notes:
            log(line)

    served = [(sv.spec.prompt, list(sv.req.tokens)) for sv in in_window]
    del eng, loop, in_window
    gc.collect()
    t5 = time.perf_counter()
    ref_w = weight_gen.make(model, seed, int8=int8)
    result = check.served_gaps(model, ref_w, served, int(mix["max_len"]),
                               control=control)
    del ref_w
    log(f"check: {result['positions']} served tokens compared in "
        f"{time.perf_counter() - t5:.2f} s")
    limit = model["limits"]["served_logit_gap"]
    gap = result["served_logit_gap"]
    if control is not None:
        log(f"the program's served_logit_gap {gap}; the control "
            f"({control}) stands in its place")
        gap = result["control_logit_gap"]
    correct = failed == 0 and limit is not None and gap <= limit
    out = {"correct": bool(correct), "attempted": len(served),
           "failed": failed, "metrics": metrics,
           "device": {"platform": device.platform, "kind": device.device_kind,
                      "count": len(jax.devices()),
                      "memory_peak_bytes": peak}}
    if trace:
        out["device"]["busy_s"] = reduced["busy_s"]
        out["device"]["window_s"] = reduced["window_s"]
        out["breakdown"] = {"device_ops": reduced["device_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
    out["checks"] = {"served_logit_gap": {"value": gap, "limit": limit},
                     "failed_requests": {"value": failed, "limit": 0}}
    log(f"served_logit_gap {gap} limit {limit}")
    log(f"failed_requests {failed} limit 0")
    return out


@dataclasses.dataclass
class MetricContext:
    """What a per-layer reader may read.  ``notes`` collects lines the
    readers want printed beside their numbers."""
    model: dict
    peaks: dict
    trace: dict
    spans: Spans
    window: tuple
    step_ends: list
    served: list
    kv0: dict
    kv1: dict
    weight_stats: dict
    notes: list = dataclasses.field(default_factory=list)

    @property
    def steps(self) -> int:
        return len(self.step_ends)

    def window_tokens(self):
        """(prompt length, token index, stamp) of every token served in
        the window; index 0 is the prefill's, and tokens of one step share
        its stamp."""
        t0 = self.window[0]
        for sv in self.served:
            n = len(sv.spec.prompt)
            for i, t in enumerate(sv.stamps):
                if t > t0:
                    yield n, i, t
