#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``) names a configuration file and a traffic
mix; the configuration names its architecture family
(``bench/families``), which gives the program's spec, the weights and
their parameter tree.  One process, on the chip it finds: it makes the
weights from the seed, builds ``ServingEngine``, warms every program the
cell's traffic will run, then drives ``submit`` and ``step`` for
``--seconds`` and reads the end-to-end metrics (``--trace 0``) or, from a
profiler trace of the window, the per-layer metrics (``--trace 1``): the
trace is reduced both from outside (``bench/trace.py``) and by the
program's own spans and named scopes (``bench/program_trace.py``), whose
step-program ops are named from the optimized HLO that XLA dumps when the
traced run compiles them (``compile_steps_here``).  After the window it frees
the program's state and checks a sample of the served tokens against
the plain reference (``bench/check.py``).  The last line of standard
output is one JSON object; the last lines of standard error give each
number compared beside its limit.

It exits non-zero, printing no result, where JAX finds no TPU or fewer
chips than the cell asks for.  Options the driver does not pass:
``--rate`` overrides an open loop's rate (the knee sweep) and
``--control int8`` serves through the program's int8 weight path (the
precision control, which must come out not correct).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

CHECKOUT = Path(__file__).resolve().parents[1]
CACHE = CHECKOUT / ".bench_cache"
SHARED = CACHE / "jax"   # the compile cache every run of the checkout reads
sys.path[:0] = [str(CHECKOUT), str(CHECKOUT / "src")]

from bench import check, program_trace, stats, traffic, weights  # noqa: E402
from bench.manifest import (Cell, family_module, load_cell,  # noqa: E402
                            metric_reader, reference_module)

HARVEST_WIDTHS = 5   # finished streams per harvest whose gathers are warmed
WARM_STEPS = 16      # the warm-up request needs 4 (2 mixed, 2 decode)
DRAIN_S = 60.0       # longest wait past the close for a first token


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class CompileMeter:
    """Counts XLA compilations and the seconds JAX spends tracing,
    lowering and compiling (``jax.monitoring`` events)."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self) -> None:
        import jax
        self.seconds = 0.0
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event in self.EVENTS:
            self.seconds += duration
        if event == self.EVENTS[2]:
            self.compiles += 1


def use_cache(path: Path) -> None:
    """JAX's persistent compilation cache at ``path``, a fixed path in the
    checkout, whatever the environment says; every program is kept."""
    import jax
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(path)
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def traced_dir(cell: Cell) -> Path:
    """A traced run's own files: the profiler trace, the optimized HLO of
    the step programs it compiles, and its view of the compile cache."""
    return CACHE / "traced" / cell.name


def compile_steps_here(own: Path, shared: Path) -> Path:
    """Set a traced run up to compile its two step programs itself, with
    XLA dumping their optimized HLO under ``own / "hlo"``: a program loaded
    from the compile cache writes no dump, and its ops would go unnamed.
    The run reads the ``shared`` cache through ``own / "jax"``, links to
    every entry but the step programs'; returns that view.  Call it before
    JAX starts its backend."""
    os.environ["XLA_FLAGS"] = " ".join([
        os.environ.get("XLA_FLAGS", ""), f"--xla_dump_to={own / 'hlo'}",
        "--xla_dump_hlo_as_text",
        "--xla_dump_hlo_module_re=.*("
        + "|".join(program_trace.STEP_PROGRAMS) + ").*"]).strip()
    view = own / "jax"
    view.mkdir(parents=True)
    steps = tuple(f"jit_{p}-" for p in program_trace.STEP_PROGRAMS)
    for path in shared.glob("*"):
        if not path.name.startswith(steps):
            (view / path.name).symlink_to(path.resolve())
    return view


def adopt(view: Path, shared: Path) -> None:
    """Move what a traced run compiled into its view of the cache over to
    the shared cache, where the checkout's other runs find it.  The dump
    flags are not part of a cache key, so the entries are the same."""
    shared.mkdir(parents=True, exist_ok=True)
    for path in view.iterdir():
        if not path.is_symlink() and not (shared / path.name).exists():
            path.replace(shared / path.name)


def find_chips(n: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < n:
        log(f"no chip for this cell: JAX reports {len(devs)} "
            f"{devs[0].platform} device(s), the cell needs {n} TPU chip(s)")
        raise SystemExit(2)
    return devs[0], len(devs)


def warm_up(engine, mix: dict, vocab: int) -> None:
    """Compile every program the window will run: the admission, the
    mixed and the decode step (one short request), and the harvest's
    gather of finished token rows, one program per (finished streams,
    longest length) pair: every output length of the mix, up to
    ``HARVEST_WIDTHS`` streams finishing together."""
    import jax
    import jax.numpy as jnp
    w = engine.chunk_size
    engine.submit([1 + i % (vocab - 1) for i in range(w + 1)],
                  max_new_tokens=3)
    for _ in range(WARM_STEPS):
        if engine.step():
            break
    else:
        log(f"warm-up request unfinished after {WARM_STEPS} steps")
    for n in range(1, min(HARVEST_WIDTHS, engine.max_batch) + 1):
        for m in traffic.output_lengths(mix):
            jax.device_get(engine.state.buf[
                jnp.asarray(list(range(n)), jnp.int32), :m])


def drive(engine, reqs, mix, seed, seconds, vocab, served):
    """The measured window.  Returns (t0, t1, t_drained, pool tokens at
    t0 and t1, due, lateness, refused)."""
    from jax.profiler import TraceAnnotation
    rate = mix["loop"] == "rate"
    now = time.perf_counter
    due, late, refused = {}, [], 0
    pending = list(reversed(reqs))
    outstanding: set[int] = set()
    admitted: set[int] = set()
    first_token: set[int] = set()

    def on_event(e):
        if e.kind == "admit":
            admitted.add(e.uid)
        elif e.kind == "first_token":
            first_token.add(e.uid)
    engine.events.subscribe(on_event)

    def submit(r, t_due):
        nonlocal refused
        try:
            uid = engine.submit(traffic.prompt_tokens(r, seed, vocab),
                                max_new_tokens=r.output_len)
        except ValueError as e:
            refused += 1
            log(f"request {r.index} refused: {e}")
            return
        due[uid] = t_due
        late.append(now() - t_due)
        outstanding.add(uid)
        served[uid] = (r, None)

    def step():
        with TraceAnnotation("bench.step"):
            for req in engine.step():
                outstanding.discard(req.uid)
                served[req.uid] = (served[req.uid][0], req.generated)

    def top_up():
        while len(outstanding - admitted) < engine.max_batch:
            if not pending:
                raise SystemExit("the backlog ran out of requests; raise "
                                 "the mix's 'requests'")
            submit(pending.pop(), now())

    if not rate:
        # the backlog opens at mixed ages: the first max_batch requests
        # carry residual lengths; every slot is seated (and, where the
        # mix says so, every residual prompt prefilled) before t0
        top_up()
        head = {u for u, (r, _) in served.items()
                if r.index < engine.max_batch}
        t_pre, n_pre = now(), 1
        step()
        while mix["open"] == "prefilled" and not head <= first_token:
            top_up()
            step()
            n_pre += 1
        log(f"before the window: {n_pre} steps in {now() - t_pre:.3f} s")
    pool0 = engine.memory_stats().used_tokens
    t0 = now()
    deadline = t0 + seconds
    with TraceAnnotation("bench.window"):
        while now() < deadline:
            with TraceAnnotation("bench.submit"):
                if rate:
                    while pending and t0 + pending[-1].due <= now():
                        r = pending.pop()
                        submit(r, t0 + r.due)
                else:
                    top_up()
            if outstanding:
                step()
            else:
                nxt = t0 + pending[-1].due if pending else deadline
                with TraceAnnotation("bench.sleep"):
                    time.sleep(max(0.0, min(nxt, deadline) - now()))
    t1 = now()
    pool1 = engine.memory_stats().used_tokens
    log(f"backlog at close: {len(outstanding - admitted)} waiting, "
        f"{len(outstanding & admitted)} in slots")
    if rate:
        # no new arrivals; wait (at most DRAIN_S) for the first token of
        # every request due in the window, so its TTFT is whole
        n = 0
        while (set(due) - first_token) & outstanding \
                and now() < t1 + DRAIN_S:
            step()
            n += 1
        log(f"after the window: {n} steps in {now() - t1:.3f} s until "
            "every request due in it had its first token")
    return t0, t1, now(), (pool0, pool1), due, late, refused


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             device_kind: str, control: str | None = None,
             rate: float | None = None, peaks: dict | None = None):
    """Everything of one run after the chip check.  Returns the result
    object without its ``device`` entry, and the stderr diagnostics."""
    import jax

    from bench.trace import load_events, reduce_events
    from repro.models.model import Model
    from repro.serving.engine import ServingEngine
    from repro.serving.events import SCOPE_NAMES, EventLog
    from repro.serving.sampling import SamplingParams

    cfg, mix = cell.config, dict(cell.traffic)
    if rate is not None:
        mix["rate_per_s"] = rate
    sv = cfg["serving"]
    if traffic.max_total_len(mix) > sv["max_len"]:
        raise SystemExit(f"mix {cell.name}: a request can need "
                         f"{traffic.max_total_len(mix)} positions, the "
                         f"configuration holds {sv['max_len']}")
    if peaks is None:
        table = json.loads((CHECKOUT / "bench" / "peaks.json").read_text())
        if device_kind not in table:
            raise SystemExit(f"device {device_kind!r} is not in "
                             "bench/peaks.json")
        peaks = table[device_kind]
    meter = CompileMeter()
    fam = family_module(cfg["reference"], cell.root)
    spec = fam.spec(cfg, control)
    w = weights.from_seed(fam, cfg, seed)
    params = fam.to_program(cfg, w)
    weights.check_tree(Model.from_spec(spec).abstract(),
                       jax.eval_shape(lambda p: p, params))
    engine = ServingEngine(spec, sampling=SamplingParams(temperature=0.0))
    engine.load(params)
    del w, params
    vocab = cfg["vocab_size"]
    reqs = traffic.requests(mix, seed, seconds, sv["max_batch"])
    warm_up(engine, mix, vocab)
    evlog = EventLog()
    engine.events.subscribe(evlog)
    served: dict = {}
    trace_dir = traced_dir(cell) / "trace"
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    setup_compiles, setup_compile_s = meter.compiles, meter.seconds
    setup_end = time.perf_counter()
    t0, t1, t_drained, pool, due, late, refused = drive(
        engine, reqs, mix, seed, seconds, vocab, served)
    window_compiles = meter.compiles - setup_compiles
    if trace:
        jax.profiler.stop_trace()
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    # the program's state goes before the reference runs
    del engine
    gc.collect()
    jax.clear_caches()
    live = sum(a.nbytes for a in jax.live_arrays())

    rec = stats.Record(
        events=[(e.kind, e.uid, e.step, e.t, e.data) for e in evlog.events],
        due=due, prompt_len={u: served[u][0].prompt_len for u in due},
        t0=t0, t1=t1, t_drained=t_drained, loop=mix["loop"],
        max_batch=sv["max_batch"],
        config=cfg, peaks=peaks, setup_s=setup_end - T_START,
        pool_tokens=pool, family=fam)
    diag = [f"setup: {setup_end - T_START:.3f} s to the window, of which "
            f"{setup_compile_s:.3f} s tracing/compiling "
            f"({setup_compiles} compilations)",
            f"window: {t1 - t0:.3f} s, {len(due)} requests submitted, "
            f"{refused} refused, {window_compiles} compilations inside",
            f"memory: peak {peak} bytes in use; {live} bytes live once the "
            "program's state was freed"]
    if mix["loop"] == "rate" and late:
        diag.append("generator lateness s: p50 "
                    f"{stats.percentile(late, 50):.6f} p99 "
                    f"{stats.percentile(late, 99):.6f} max {max(late):.6f}")
    if trace:
        events = load_events(str(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        rec.trace = reduce_events(events)
        rec.program_trace = program_trace.reduce_program(
            events, SCOPE_NAMES,
            program_trace.load_hlo(traced_dir(cell) / "hlo"))
        del events
        progs: dict = {}
        for _, name, _, d in rec.trace.modules:
            progs[name] = progs.get(name, 0.0) + d / 1e9
        diag.append("trace: devices " + ", ".join(rec.trace.devices)
                    + "; programs by device seconds: " + ", ".join(
                        f"{n} {s:.4f}" for n, s in sorted(
                            progs.items(), key=lambda kv: -kv[1])[:6]))
        diag += program_trace.diag(rec.program_trace)
    names = [m["name"] for m in (cell.per_layer if trace else cell.end_to_end)]
    metrics = {}
    units = {m["name"]: m["unit"] for m in cell.per_layer + cell.end_to_end}
    for name in names:
        v = metric_reader(name, cell.root)(rec)
        if v is not None:
            metrics[name] = {"value": v, "unit": units[name]}

    # correctness, on the finished requests of the window
    fin_t = {uid: t for kind, uid, _, t, _ in rec.events if kind == "finish"}
    done = [check.Served(u, traffic.prompt_tokens(r, seed, vocab), toks)
            for u, (r, toks) in served.items()
            if toks is not None and u in due and t0 <= fin_t.get(u, -1) <= t1]
    mismatch = sum(len(s.tokens) != served[s.uid][0].output_len for s in done)
    ck = cfg["check"]
    sample = check.draw(done, seed, ck["min_served_tokens"],
                        ck["max_requests"])
    t_ref = time.perf_counter()
    gaps: dict = {}
    if sample:
        ref = reference_module(cfg["reference"], cell.root)
        w = weights.from_seed(fam, cfg, seed)
        fn = jax.jit(lambda w, *a: ref.gaps(cfg, w, *a)[0])
        gaps = check.read_gaps(check.pack(sample, sv["max_len"]),
                               lambda *a: fn(w, *a))
        del w
    read = check.summary(gaps)
    limits = cell.limits
    checks = {k: {"value": v, "limit": limits[k]} for k, v in read.items()
              if k in limits}
    checks["length_mismatches"] = {"value": mismatch, "limit": 0}
    checks["window_compiles"] = {"value": window_compiles, "limit": 0}
    # no served token read back is a failure, whatever else holds
    correct = bool(gaps) and all(
        c["value"] is not None and c["value"] <= c["limit"]
        for c in checks.values())
    over = sum(any(g > limits.get("max_logit_gap", float("inf"))
                   for g in gs) for gs in gaps.values())
    diag += [f"{k} {v}" for k, v in read.items() if k not in limits]
    diag.append(f"check: {len(sample)} of {len(done)} finished requests, "
                f"{sum(len(s.tokens) for s in sample)} served tokens, "
                f"reference {time.perf_counter() - t_ref:.3f} s")
    result = {"correct": correct, "attempted": len(due) + refused,
              "failed": refused + mismatch + over,
              "metrics": metrics, "memory_peak_bytes": peak}
    if trace:
        tr = rec.trace
        result["busy_s"], result["window_s"] = tr.busy_s(), tr.window_s
        result["breakdown"] = {"device_ops": tr.top_ops(),
                               "idle_gaps": tr.idle_gaps()}
    return result, checks, diag


def serve(cell: Cell, seed: int, seconds: float, trace: bool,
          control: str | None = None, rate: float | None = None) -> None:
    """One run of ``cell`` on the chip this process finds: the result
    line on standard output, the diagnostics and each number compared
    beside its limit as the last lines of standard error."""
    # the TPU runtime logs to /tmp unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    own = traced_dir(cell)
    shutil.rmtree(own, ignore_errors=True)
    use_cache(compile_steps_here(own, SHARED) if trace else SHARED)
    try:
        device, count = find_chips(cell.chips)
        result, checks, diag = run_cell(
            cell, seed, seconds, trace, device.device_kind,
            control=control, rate=rate)
    finally:
        if trace:
            adopt(own / "jax", SHARED)
        shutil.rmtree(own, ignore_errors=True)
    dev = {"platform": device.platform, "kind": device.device_kind,
           "count": count,
           "memory_peak_bytes": result.pop("memory_peak_bytes")}
    if trace:
        dev["busy_s"], dev["window_s"] = result.pop("busy_s"), \
            result.pop("window_s")
    out = {"correct": result["correct"], "attempted": result["attempted"],
           "failed": result["failed"], "metrics": result["metrics"],
           "device": dev}
    if "breakdown" in result:
        out["breakdown"] = result["breakdown"]
    out["checks"] = checks
    for line in diag:
        log(line)
    for name, c in checks.items():
        log(f"{name} {c['value']} limit {c['limit']}")
    print(json.dumps(out))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rate", type=float, default=None,
                    help="override an open loop's arrival rate (req/s)")
    ap.add_argument("--control", choices=("int8",), default=None,
                    help="serve through the program's int8 weight path")
    args = ap.parse_args(argv)
    serve(load_cell(args.workload), args.seed, args.seconds,
          bool(args.trace), control=args.control, rate=args.rate)


if __name__ == "__main__":
    main()
