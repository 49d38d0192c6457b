"""Runs one benchmark cell: builds the served model from its configuration
file, drives the serving engine with the cell's traffic for a window on the
benchmark's clock, reads the metrics, and checks what the window served
against the plain reference.

The entry the window drives is ``repro.serve.ContinuousBatchingEngine.serve``,
built as ``repro.launch.serve --continuous`` builds it: paged KV pool, fused
Pallas paged-decode kernel, chunked prefill, prefix cache, and in an AMP
configuration the solved plan passed as ``mp=``.

Adding a configuration takes new files only, each found by a name:

* ``bench/configs/<config>.json``: the model's published ``config.json``,
  key for key, with any key cut to size listed under ``reduced`` in
  ``BENCHMARK.json``, plus ``reference`` (the module below), ``weight_seed``,
  ``served_dtype``, ``deployment`` (what the cut stands for) and, to serve
  under a plan, ``amp``;
* ``bench/configs/<reference>.py``: the plain reference, which imports
  nothing of the program: ``param_layout(cfg)`` (path -> shape, dtype, init
  kind; checked against the served model's parameters) and
  ``logits_at(weights, cfg, ids, rows, t_pad)``, computed in blocks so that
  it fits beside the served weights;
* for a family (``model_type``) not served yet, ``bench/models/<model_type>.py``
  with ``lm_config(name, cfg)``, the program's model configuration for the
  file, refusing by name each key it cannot serve; and
  ``bench/costs/<model_type>.py``, its step costs (``bench/flops.py``);
* ``bench/traffic/<mix>.json``: the mix (``bench/traffic.py``);
* ``bench/limits/<workload>.json``: the limits of ``correct``;
* ``bench/metrics/<metric>.py``: a reader for each new metric;
* the ``configs``, ``workloads`` and metric entries in ``BENCHMARK.json``.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib
import importlib.util
import json
import math
import sys
import threading
import time
from pathlib import Path
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import check, stats, traffic
from bench import trace as tr
from bench.weights import make_weights

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
CACHE = ROOT / ".bench_cache"
OPEN_TIMEOUT_S = 240.0        # the window must open within this long
TRACE_SECONDS = 8.0           # longest traced stretch of a --trace 1 run
CALIB_SEED = 0                # calibration batches' token content


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_file_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class CompileClock:
    """Backend compiles and persistent-cache hits, from JAX's monitoring
    events. Listeners stay registered for the life of the process, so make
    one per process."""

    def __init__(self):
        self.seconds, self.n, self.hits = 0.0, 0, 0
        self.times: list = []
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.n += 1
            self.times.append(time.perf_counter())

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def mark(self) -> tuple:
        return self.seconds, self.n, self.hits

    def since(self, mark: tuple) -> dict:
        s, n, h = mark
        return {"compile_s": round(self.seconds - s, 3),
                "compiles": self.n - n, "cache_hits": self.hits - h}

    def compiles_between(self, lo: float, hi: float) -> int:
        return sum(1 for t in self.times if lo <= t < hi)


class GcLog:
    """Passes of Python's cyclic garbage collector while installed, from
    ``gc.callbacks``: (generation, start, end)."""

    def __init__(self):
        self.passes: list = []
        self._start = 0.0

    def _cb(self, phase, info):
        if phase == "start":
            self._start = time.perf_counter()
        else:
            self.passes.append((info["generation"], self._start,
                                time.perf_counter()))

    def __enter__(self) -> "GcLog":
        gc.callbacks.append(self._cb)
        return self

    def __exit__(self, *exc) -> None:
        gc.callbacks.remove(self._cb)

    def counters(self, lo: float, hi: float) -> dict:
        """Passes that started in [lo, hi)."""
        inside = [(g, b - a) for g, a, b in self.passes if lo <= a < hi]
        return {"gc_passes": len(inside),
                "gc_full_passes": sum(1 for g, _ in inside if g == 2),
                "gc_longest_s": round(max((d for _, d in inside),
                                          default=0.0), 4)}


class StepLog:
    """Inputs of every serving step dispatched in a traced run, recorded
    around the engine's step calls: prefill row widths and lengths, and the
    live positions of decode rows. Installed only with ``--trace 1``."""

    def __init__(self):
        self.prefill: list = []   # (t, bucket, start (B,), valid (B,))
        self.decode: list = []    # (t, positions of live rows)

    def install(self, eng) -> tuple:
        """Wrap the engine's steps; returns the originals."""
        pre, dec = eng.prefill_chunk_step, eng.decode_step

        def prefill(params, caches, tokens, start, valid, *rest):
            self.prefill.append((time.perf_counter(), int(tokens.shape[1]),
                                 np.asarray(start), np.asarray(valid)))
            return pre(params, caches, tokens, start, valid, *rest)

        def decode(params, caches, token, pos, bt):
            live = np.asarray(bt)[:, 0] >= 0
            self.decode.append((time.perf_counter(), np.asarray(pos)[live]))
            return dec(params, caches, token, pos, bt)

        eng.prefill_chunk_step, eng.decode_step = prefill, decode
        return pre, dec

    def between(self, lo: float, hi: float) -> "StepLog":
        out = StepLog()
        out.prefill = [s for s in self.prefill if lo <= s[0] < hi]
        out.decode = [s for s in self.decode if lo <= s[0] < hi]
        return out


@dataclasses.dataclass
class RunData:
    """What a metric reader sees of one run. Times are ``perf_counter``
    seconds; ``token_times[rid]`` lists the delivery time of each token."""
    workload: str
    config: dict
    mix: dict
    seconds: float
    setup_s: float
    t_open: float
    t_close: float
    token_times: dict
    counters: dict                   # drain counters, compiles, gc passes
    plan: Optional[dict] = None      # n_ops, n_quantized, predicted_gain
    peaks: object = None
    steps: Optional[StepLog] = None  # trace runs: steps of the window
    trace: Optional[tr.Trace] = None
    trace_window: Optional[tuple] = None   # (lo, hi) on the trace's clock


def engine_counters(summary) -> dict:
    keys = ("prefill_chunks", "prefill_tokens", "prefix_hit_tokens",
            "decode_live_token_steps", "decode_capacity_token_steps",
            "preemptions", "n_failed", "n_retried")
    out = {k: summary.counters.get(k, 0) for k in keys}
    out["decode_steps"] = summary.n_steps
    out["faults_seen"] = sum(summary.counters["faults"]["seen"].values())
    return out


def family_module(package: str, cfg: dict):
    """``bench/<package>/<model_type>.py`` for the configuration's family,
    or None where there is none."""
    family = str(cfg.get("model_type", ""))
    name = f"bench.{package}.{family}"
    if not family.isidentifier() or importlib.util.find_spec(name) is None:
        return None
    return importlib.import_module(name)


def lm_config(name: str, cfg: dict):
    """The program's model configuration for a configuration file, by its
    family's module ``bench/models/<model_type>.py``."""
    family = family_module("models", cfg)
    if family is None:
        raise ValueError(f"model_type {cfg.get('model_type')!r}: no "
                         f"bench/models/<model_type>.py serves it")
    return family.lm_config(name, cfg)


def served_model(name: str, cfg: dict, reference) -> tuple:
    """(model, weights) of a configuration: the program's model, and the
    reference's weight layout, checked against the model's parameters and
    made from the file's ``weight_seed``."""
    from repro.models.registry import build_model
    from repro.nn.spec import flatten_paths
    model = build_model(lm_config(name, cfg))
    layout = reference.param_layout(cfg)
    want = {p: (tuple(s.shape), str(s.dtype)) for p, s in
            flatten_paths(model.abstract_params()).items()}
    have = {p: (tuple(v[0]), v[1]) for p, v in layout.items()}
    if want != have:
        diff = sorted(set(want.items()) ^ set(have.items()))[:6]
        raise ValueError(f"reference layout differs from the served "
                         f"model's parameters: {diff}")
    return model, make_weights(layout, int(cfg["weight_seed"]))


def calibration_batches(vocab: int, amp: dict) -> list:
    rng = np.random.default_rng(CALIB_SEED)
    out = []
    for _ in range(amp["calibration_batches"]):
        t = rng.integers(0, vocab, (1, amp["calibration_tokens"] + 1),
                         dtype=np.int32)
        out.append({"tokens": jnp.asarray(t[:, :-1]),
                    "labels": jnp.asarray(t[:, 1:])})
    return out


def probe_op_chunk(model, params, batch) -> int:
    """Ops per calibration backward pass, so that one pass's probes take
    about a third of the free device memory. Each operand element of a
    probed op costs an f32 probe, its f32 gradient and a bf16 capture."""
    from repro.core.sensitivity import collect_ops
    ops = collect_ops(lambda p, b, ctx: model.loss(p, b, ctx), params, batch)
    need = sum(10 * (math.prod(o.lhs_shape) + math.prod(o.rhs_shape))
               for o in ops)
    stats = jax.devices()[0].memory_stats() or {}
    if "bytes_limit" not in stats:
        return len(ops)
    free = stats["bytes_limit"] - stats["bytes_in_use"]
    return math.ceil(len(ops) / max(1, math.ceil(need / (free / 3))))


class Server:
    """The served model of one configuration and the engine settings of one
    traffic mix: weights, plan and compiled programs, made once and reused
    by every run of the process."""

    def __init__(self, config_name: str, config: dict, mix: dict,
                 clock: CompileClock, reference=None):
        self.config_name, self.config, self.mix = config_name, config, mix
        self.clock = clock
        self.ref = reference or load_module(
            BENCH / "configs" / f"{config['reference']}.py")
        self.parts: dict = {}
        t = time.perf_counter()
        self.model, self.params = served_model(config_name, config,
                                               self.ref)
        jax.block_until_ready(self.params)
        self.parts["weights_s"] = time.perf_counter() - t
        self.plan, self.plan_info = None, None
        if "amp" in config:
            t = time.perf_counter()
            self.plan, self.plan_info = self._solve_plan(config["amp"])
            self.parts["plan_s"] = time.perf_counter() - t
        t = time.perf_counter()
        self.engine = self.make_engine()
        self.warm_up()
        self.parts["warmup_s"] = time.perf_counter() - t

    def _solve_plan(self, amp: dict):
        from repro.core.pipeline import AMPOptions, calibrate
        opts = AMPOptions(tau=amp["tau"], objective=amp["objective"])
        path = CACHE / "amp" / f"{self.config_name}.bundle.npz"
        path.parent.mkdir(parents=True, exist_ok=True)
        batches = calibration_batches(self.config["vocab_size"], amp)
        op_chunk = (None if path.exists()
                    else probe_op_chunk(self.model, self.params, batches[0]))
        bundle = calibrate(self.model, self.params, batches, opts,
                           op_chunk=op_chunk, cache=str(path))
        plan = bundle.solve(tau=amp["tau"], objective=amp["objective"])
        if plan.predicted_loss_mse > plan.budget:
            raise RuntimeError(f"plan's predicted loss MSE "
                               f"{plan.predicted_loss_mse:.3e} exceeds its "
                               f"budget {plan.budget:.3e}")
        return plan, {"n_ops": len(bundle.sens.ops),
                      "n_quantized": plan.n_quantized,
                      "predicted_gain": float(plan.predicted_gain),
                      "predicted_loss_mse": float(plan.predicted_loss_mse),
                      "gain_tier": plan.meta.get("gain_tier")}

    def make_engine(self):
        from repro.serve import ContinuousBatchingEngine
        s = self.mix["server"]
        return ContinuousBatchingEngine(
            self.model, n_slots=s["n_slots"], max_len=s["max_len"],
            mp=self.plan, block_size=s["block_size"], n_blocks=s["n_blocks"],
            chunk_len=s["chunk_len"], chunk_budget=s["chunk_budget"],
            prefix_cache=True)

    def warm_up(self) -> None:
        """Compile every program the window runs: the prefill step at each
        bucket the chunk length allows, the decode step, the token and
        tripwire readers, and the copy-on-write block copy (a prompt served
        twice forks its last shared block)."""
        from repro.serve import Request
        s = self.mix["server"]
        rng = np.random.default_rng(12345)
        vocab = self.config["vocab_size"]
        buckets, b = [], 8
        while b < s["chunk_len"]:
            buckets.append(b)
            b *= 2
        buckets.append(s["chunk_len"])
        twice = rng.integers(0, vocab, 2 * s["block_size"], dtype=np.int32)
        prompts = [rng.integers(0, vocab, n, dtype=np.int32)
                   for n in buckets] + [twice, twice]
        for p in prompts:
            self.engine.serve(self.params, [Request(0, p, 2)])

    # ------------------------------------------------------------------
    def run(self, seed: int, seconds: float, trace: bool,
            setup_started: Optional[float] = None) -> tuple:
        """One measured window. Returns (RunData, results by rid, items)."""
        from repro.serve import Request
        t_gen = time.perf_counter()
        items = traffic.generate(self.mix, self.config["vocab_size"], seed)
        self.parts["traffic_s"] = time.perf_counter() - t_gen
        if self.engine is None:
            self.engine = self.make_engine()
        eng = self.engine
        steps = originals = None
        if trace:
            steps = StepLog()
            originals = steps.install(eng)
        if self.mix["system_prompts"].get("cached_at_start"):
            sys_prompts = traffic.system_prompts(
                self.mix, self.config["vocab_size"], seed)
            eng.serve(self.params, [Request(rid=i, tokens=p, max_new_tokens=1)
                                    for i, p in enumerate(sys_prompts)])
        token_times = {it.rid: [] for it in items}
        # set-up's objects (traced programs, the model, the traffic) leave
        # the collector's view: a full pass over them (about 400k objects)
        # would otherwise land in some windows and not in others
        gc.collect()
        gc.freeze()
        t_ready = time.perf_counter()
        setup_s = (t_ready - setup_started if setup_started is not None
                   else None)
        trace_dir = CACHE / "traces" / "window"
        tracer = _Tracer(trace_dir) if trace else None
        with GcLog() as gc_log:
            summary, t_open, t_close = self._backlog(eng, items, seconds,
                                                     token_times, tracer)
        if originals is not None:
            eng.prefill_chunk_step, eng.decode_step = originals
        results = dict(summary.results)
        data = RunData(workload="", config=self.config, mix=self.mix,
                       seconds=seconds, setup_s=setup_s, t_open=t_open,
                       t_close=t_close, token_times=token_times,
                       counters=engine_counters(summary),
                       plan=self.plan_info,
                       steps=None if steps is None
                       else steps.between(t_open, t_close))
        data.counters["compiles_in_window"] = self.clock.compiles_between(
            t_open, t_close)
        data.counters.update(gc_log.counters(t_open, t_close))
        if tracer is not None:
            data.trace, data.trace_window = tracer.reduce()
        return data, results, items

    def _backlog(self, eng, items, seconds, token_times, tracer):
        """Every request due at the start; the window opens once every
        first-wave request (one per slot) has its first token, and closes
        ``seconds`` later on the benchmark's clock."""
        from repro.serve import Request
        n_first = min(self.mix["server"]["n_slots"], len(items))
        pending = [n_first]
        opened = threading.Event()
        t_open = [None]

        def on_token(rid, idx, tok):
            t = time.perf_counter()
            token_times[rid].append(t)
            if idx == 0 and rid < n_first:
                pending[0] -= 1
                if pending[0] == 0:
                    t_open[0] = t
                    opened.set()

        def monitor():
            if not opened.wait(OPEN_TIMEOUT_S):
                eng.shutdown()
                return
            close = t_open[0] + seconds
            if tracer is not None:
                tracer.record(min(TRACE_SECONDS, seconds))
            _sleep_until(close)
            eng.shutdown()

        watcher = threading.Thread(target=monitor, name="bench-window")
        watcher.start()
        try:
            reqs = [Request(rid=it.rid, tokens=it.tokens,
                            max_new_tokens=it.max_new_tokens) for it in items]
            summary = eng.serve(self.params, reqs, on_token=on_token)
        finally:
            eng.shutdown()
            opened.set()
            watcher.join()
        if t_open[0] is None:
            raise RuntimeError("the window never opened: the first wave did "
                               "not start within "
                               f"{OPEN_TIMEOUT_S:g} s")
        return summary, t_open[0], t_open[0] + seconds

    def release(self) -> None:
        """Drop the engine and its KV pool, so the reference can use the
        device memory the window used."""
        self.engine = None
        gc.unfreeze()
        gc.collect()


class _Tracer:
    """A profiler trace of part of the window, and its reduction."""

    def __init__(self, log_dir: Path):
        self.log_dir = log_dir
        self.host_window = None

    def record(self, seconds: float) -> None:
        import shutil
        shutil.rmtree(self.log_dir, ignore_errors=True)
        # no Python function tracing: it would slow the host it measures
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(self.log_dir), profiler_options=opts)
        t0 = time.perf_counter()
        _sleep_until(t0 + seconds)
        t1 = time.perf_counter()
        jax.profiler.stop_trace()
        self.host_window = (t0, t1)

    def reduce(self) -> tuple:
        if self.host_window is None:
            raise RuntimeError("the trace never started")
        data = tr.load(tr.find_xplane(str(self.log_dir)))
        if not data.ops:
            raise RuntimeError("the trace holds no device operations")
        lo, hi = tr.span(data)
        host = self.host_window[1] - self.host_window[0]
        # the host clock bounds the traced stretch; device events give its
        # position on the trace's clock
        if host > hi - lo:
            hi = lo + host
        return data, (lo, hi)


def _sleep_until(t: float) -> None:
    while True:
        left = t - time.perf_counter()
        if left <= 0:
            return
        time.sleep(min(left, 0.05))


# ----------------------------------------------------------------------
def check_run(server: Server, results: dict, items: list, seed: int) -> dict:
    """The comparison that decides ``correct``: see ``bench/check.py``."""
    rng = np.random.default_rng([seed, 3])
    sample = check.sample(check.finished(results, items), rng,
                          **server.mix["check"])
    s = server.mix["server"]
    gaps = check.reference_gaps(
        lambda ids, rows: server.ref.logits_at(server.params, server.config,
                                               ids, rows, s["max_len"]),
        sample, server.mix["answer_tokens"]["max"])
    return dict(check.summary(gaps), sampled_requests=len(sample))


def verdict(checks: dict, limits: dict, counters: dict) -> tuple:
    """(correct, {name: {value, limit}}): every compared number beside its
    limit."""
    shown = {"mean_logit_gap": {"value": checks["mean_logit_gap"],
                                "limit": limits["mean_logit_gap"]},
             "sampled_tokens": {"value": checks["tokens"],
                                "limit": limits["min_sampled_tokens"]},
             "faults_seen": {"value": counters["faults_seen"], "limit": 0}}
    ok = (checks["mean_logit_gap"] is not None
          and checks["mean_logit_gap"] <= limits["mean_logit_gap"]
          and checks["tokens"] >= limits["min_sampled_tokens"]
          and counters["faults_seen"] == 0)
    return bool(ok), shown


def attempted_failed(data: RunData, results: dict) -> tuple:
    """Requests the window served: those with a token delivered in it;
    failed are those of them the engine failed."""
    lo, hi = data.t_open, data.t_close
    rids = {rid for rid, ts in data.token_times.items()
            if any(lo <= t < hi for t in ts)}
    failed = {rid for rid in rids
              if rid in results and results[rid].status == "failed"}
    return len(rids), len(failed)


# ----------------------------------------------------------------------
def metric_specs(spec: dict, workload: str, trace: bool) -> list:
    """The metrics a cell reports: its end-to-end metrics with
    ``--trace 0``, its per-layer metrics with ``--trace 1``."""
    e2e = [m for m in spec["end_to_end"]
           if workload in m.get("workloads", [workload])]
    if not trace:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if (workload in m["workloads"] if "workloads" in m
                else m["moves"] in moved)]


def read_metrics(specs: list, data: RunData) -> dict:
    out = {}
    for m in specs:
        value = load_module(BENCH / "metrics" / f"{m['name']}.py").read(data)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def memory_peak() -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks))


def execute(spec: dict, workload: str, config: dict, mix: dict,
            limits: dict, seed: int, seconds: float, trace: bool, peaks,
            device: dict, setup_started: float, clock: CompileClock) -> tuple:
    """One run of one cell. Returns (result line, check lines)."""
    cell = next(w for w in spec["workloads"] if w["name"] == workload)
    mark = clock.mark()
    server = Server(cell["config"], config, mix, clock)
    data, results, items = server.run(seed, seconds, trace, setup_started)
    data.workload, data.peaks = workload, peaks
    parts = {k: round(v, 3) for k, v in server.parts.items()}
    log("setup", json.dumps({"setup_s": round(data.setup_s, 3), **parts,
                             **clock.since(mark),
                             "plan": server.plan_info}))
    log("window", json.dumps({
        "seconds": seconds,
        "tokens_per_5s": stats.per_slice(data.token_times, data.t_open,
                                         data.t_close, 5.0),
        "longest_silences_s": stats.silences(data.token_times, data.t_open,
                                             data.t_close, 3),
        "open_after_serving_s": round(data.t_open - (setup_started
                                                     + data.setup_s), 3),
        "tokens": sum(1 for ts in data.token_times.values() for t in ts
                      if data.t_open <= t < data.t_close),
        **data.counters}))
    device = dict(device, memory_peak_bytes=memory_peak())
    metrics = read_metrics(metric_specs(spec, workload, trace), data)
    breakdown = None
    if trace:
        lo, hi = data.trace_window
        device["busy_s"] = tr.device_busy(data.trace, lo, hi)
        device["window_s"] = hi - lo
        breakdown = {"device_ops": tr.top_ops(data.trace),
                     "idle_gaps": tr.idle_gaps(data.trace, lo, hi)}
        data.trace = None
    attempted, failed = attempted_failed(data, results)
    server.release()
    checks = check_run(server, results, items, seed)
    log("check", json.dumps(checks))
    correct, shown = verdict(checks, limits, data.counters)
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = shown
    lines = [f"check {k} {v['value']} limit {v['limit']}"
             for k, v in shown.items()]
    return result, lines
