"""Serving launcher: one-shot batch or continuous-batching serving under an
optional MP plan — or an MP plan solved *at serve time* from a saved
calibration bundle.

    # one-shot (the paper's TTFT measurement harness)
    PYTHONPATH=src python -m repro.launch.serve --arch llama3_1b --smoke \
        --mp-plan plan.json --batch 4 --new-tokens 16

    # solve per serving SLA from a calibrate() artifact — no recalibration
    PYTHONPATH=src python -m repro.launch.serve --arch llama3_1b --smoke \
        --calibration bundle.npz --tau 0.01 --objective ET

    # continuous batching: staggered arrivals drain through a paged KV pool
    PYTHONPATH=src python -m repro.launch.serve --arch llama3_1b --smoke \
        --continuous --n-slots 4 --requests 12 --arrival-every 2 \
        --block-size 16 --n-blocks 24        # (--dense-slots for the old rings)

Loads params from a checkpoint directory if given, else random-init (smoke
demos). An ``--mp-plan`` json (saved by ``MPPlan.save``) flows straight into
either engine; ``--calibration`` loads a ``CalibrationBundle`` and runs the
cheap IP for the requested ``--tau`` / ``--objective`` right here. Reports
TTFT (the paper's measured quantity) and decode throughput.
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.core.mpconfig import MPPlan
from repro.core.pipeline import CalibrationBundle
from repro.launch.compile_cache import enable_compile_cache
from repro.models.registry import get_model
from repro.serve import ContinuousBatchingEngine, Request, ServeEngine


def _serving_op_names(model, params):
    """Abstract-trace the serving prefill; returns its op-name set, or None
    when the arch keeps a separate serving op namespace."""
    from repro.models.encdec import EncDec
    from repro.quant.qops import QuantContext
    if isinstance(model, EncDec):
        return None  # encoder-decoder serving keeps its own op namespace
    registry: list = []
    ctx = QuantContext(mode="plain", registry=registry)
    tokens = jax.ShapeDtypeStruct((1, 8), jnp.int32)
    caches = model.init_cache(1, 16, abstract=True)
    jax.eval_shape(lambda p, t, c: model.prefill(p, t, c, ctx),
                   params, tokens, caches)
    return {op.name for op in registry}


def _plan_unknown_ops(model, params, plan: MPPlan) -> set:
    """Flag plan ops this model lacks (plan solved for a different arch)."""
    known = _serving_op_names(model, params)
    return set() if known is None else plan.unknown_ops(known)


def _check_bundle_ops(model, params, bundle: CalibrationBundle,
                      src: str) -> None:
    """Validate the artifact against this model's op namespace."""
    known = _serving_op_names(model, params)
    if known is not None:
        unknown = bundle.unknown_ops(known)
        if unknown:
            raise SystemExit(
                f"[serve] calibration bundle ({src}) has {len(unknown)} ops "
                f"not in this model (e.g. {sorted(unknown)[:3]}); was it "
                f"calibrated for a different arch?")


def _solve_from_bundle(bundle: CalibrationBundle, args, src: str) -> MPPlan:
    """Serve-time solve: run the cheap IP for the requested SLA."""
    plan = bundle.solve(tau=args.tau, objective=args.objective)
    tier = plan.meta.get("gain_tier", "analytic")
    print(f"[serve] solved from {src}: tau {plan.tau} "
          f"objective {plan.objective} -> {plan.n_quantized} ops quantized "
          f"(predicted gain {plan.predicted_gain:.3e} [{tier}], "
          f"MSE {plan.predicted_loss_mse:.3e} <= {plan.budget:.3e})")
    if tier == "roofline_fallback":
        print("[serve] note: no measured wall-clock gain table in this "
              "bundle — the solve used roofline gains (run "
              "tabulate_measured_gains + re-save to upgrade)")
    return plan


def _registry_bundle(model, params, path: str):
    """Serve-time registry lookup: the freshest bundle compatible with the
    arch and the *actual* restored params' fingerprint."""
    from repro.core.pipeline import _params_fingerprint
    from repro.core.registry import BundleRegistry
    arch = getattr(model.cfg, "name", None)
    fp = _params_fingerprint(params)
    bundle = BundleRegistry(path).find(arch, fp)
    src = f"{path}:{arch}/{fp}"
    print(f"[serve] registry match: arch {arch} fingerprint {fp} "
          f"(calib_hash {bundle.meta.get('calib_hash')})")
    return bundle, src


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3_1b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--mp-plan", default=None, help="MPPlan json path")
    ap.add_argument("--calibration", default=None,
                    help="CalibrationBundle path (json/npz): solve the IP at "
                         "serve time instead of loading a fixed plan")
    ap.add_argument("--tau", type=float, default=None,
                    help="loss-MSE threshold for --calibration solves "
                         "(default: the bundle's calibration-time tau)")
    ap.add_argument("--objective", default=None, choices=("ET", "TT", "M"),
                    help="IP objective for --calibration solves")
    ap.add_argument("--registry", default=None,
                    help="bundle registry root: pick the freshest "
                         "calibration bundle compatible with this arch and "
                         "the restored checkpoint's fingerprint, instead of "
                         "trusting a hand-passed --calibration path")
    ap.add_argument("--adaptive-tau", type=float, default=None,
                    help="enable load-adaptive MP (continuous mode; needs "
                         "--calibration or --registry): serve under a tau "
                         "ladder starting at this base, escalating to more "
                         "aggressive plans as the queue grows and restoring "
                         "as it drains")
    ap.add_argument("--adaptive-levels", type=int, default=3,
                    help="tau ladder depth (base * factor**i)")
    ap.add_argument("--adaptive-factor", type=float, default=2.0)
    ap.add_argument("--adaptive-every", type=int, default=2,
                    help="controller evaluation cadence in engine ticks")
    ap.add_argument("--adaptive-dwell", type=int, default=4,
                    help="min ticks between plan swaps")
    ap.add_argument("--adaptive-queue-high", type=int, default=2,
                    help="queue-depth watermark that triggers escalation")
    ap.add_argument("--adaptive-queue-low", type=int, default=0,
                    help="queue-depth watermark below which to restore")
    ap.add_argument("--inject-faults", default=None,
                    help="deterministic fault schedule for the continuous "
                         "engine, e.g. 'nan_page@3,alloc_fail@5:slot=1' "
                         "(kind@step[:k=v,...]); see repro.serve.FaultSpec")
    ap.add_argument("--max-retries", type=int, default=1,
                    help="bounded per-request retries after a contained "
                         "fault before the request is marked failed")
    ap.add_argument("--guardrail-every", type=int, default=None,
                    help="enable the tau-anchored numerical guardrail: run a "
                         "high-precision shadow step every N decode steps and "
                         "compare logit MSE against the active plan's "
                         "loss-MSE budget (continuous mode with an MP plan)")
    ap.add_argument("--guardrail-margin", type=float, default=4.0,
                    help="breach when shadow MSE > margin * budget")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--continuous", action="store_true",
                    help="serve a staggered request stream instead of one batch")
    ap.add_argument("--n-slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--arrival-every", type=int, default=2,
                    help="decode steps between request arrivals")
    ap.add_argument("--block-size", type=int, default=16,
                    help="paged KV block size in tokens (continuous mode)")
    ap.add_argument("--n-blocks", default=None,
                    help="paged KV pool size incl. the trash block(s): an "
                         "int, 'auto' to size from the request profile "
                         "(p95 live-block demand x headroom, see "
                         "PagedCachePool.size_n_blocks), or omit for the "
                         "worst case (never backpressures)")
    ap.add_argument("--mesh", default=None,
                    help="serving mesh spec like 'data=2,model=2' "
                         "(continuous mode; needs data*model JAX devices, "
                         "e.g. XLA_FLAGS=--xla_force_host_platform_"
                         "device_count=N on CPU); omit for single-device")
    ap.add_argument("--chunk-len", type=int, default=None,
                    help="split prompts into prefill chunks of this many "
                         "tokens, interleaved with decode steps (continuous "
                         "paged mode; prompts are length-bucketed either way)")
    ap.add_argument("--chunk-budget", type=int, default=1,
                    help="max prefill chunk steps between decode steps "
                         "(bounds per-request decode stall while a long "
                         "prompt prefills)")
    ap.add_argument("--dense-slots", action="store_true",
                    help="use monolithic per-slot rings instead of paged "
                         "KV blocks (continuous mode)")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="disable cross-request prefix sharing (paged "
                         "continuous engines content-hash admitted prompts "
                         "against resident blocks by default and skip "
                         "prefill for matched full blocks)")
    ap.add_argument("--paged-attn", default=None,
                    choices=("fused", "gather"),
                    help="paged decode attention: 'fused' (default) attends "
                         "block-major KV in place via the Pallas kernel; "
                         "'gather' keeps the reference path that "
                         "materializes logical (B, S) K/V per layer")
    ap.add_argument("--sync-engine", action="store_true",
                    help="lockstep drain: read every step's tokens back "
                         "before dispatching the next (continuous mode; "
                         "default is the pipelined drain that overlaps "
                         "token transfer with decode)")
    args = ap.parse_args()

    cache_dir = enable_compile_cache()
    dev = jax.devices()[0]
    print(f"[serve] device: {dev.platform} {dev.device_kind} x "
          f"{len(jax.devices())} | compile cache {cache_dir}")
    model = get_model(args.arch, smoke=args.smoke)
    if args.ckpt_dir:
        step, tree, _ = CheckpointManager(args.ckpt_dir).restore()
        params = jax.tree.map(jnp.asarray, tree["params"])
        print(f"[serve] restored step {step} from {args.ckpt_dir}")
    else:
        params = model.init(jax.random.key(0))
        print("[serve] random-init params (demo mode)")

    if sum(map(bool, (args.mp_plan, args.calibration, args.registry))) > 1:
        raise SystemExit("--mp-plan, --calibration and --registry are "
                         "mutually exclusive")
    if (args.tau is not None or args.objective is not None) \
            and not (args.calibration or args.registry):
        raise SystemExit("--tau/--objective select a serve-time solve and "
                         "require --calibration or --registry")
    if args.adaptive_tau is not None:
        if not (args.calibration or args.registry):
            raise SystemExit("--adaptive-tau re-solves under load and needs "
                             "--calibration or --registry")
        if not args.continuous:
            raise SystemExit("--adaptive-tau drives the continuous engine; "
                             "pass --continuous")
    if (args.inject_faults or args.guardrail_every) and not args.continuous:
        raise SystemExit("--inject-faults/--guardrail-every drive the "
                         "continuous engine; pass --continuous")
    plan = None
    controller = None
    bundle = src = None
    if args.calibration:
        bundle, src = CalibrationBundle.load(args.calibration), args.calibration
    elif args.registry:
        bundle, src = _registry_bundle(model, params, args.registry)
    if bundle is not None:
        _check_bundle_ops(model, params, bundle, src)
        if args.adaptive_tau is not None:
            from repro.serve import AdaptiveMPController
            controller = AdaptiveMPController.from_bundle(
                bundle, args.adaptive_tau,
                n_levels=args.adaptive_levels, factor=args.adaptive_factor,
                objective=args.objective or "ET",
                every=args.adaptive_every, dwell=args.adaptive_dwell,
                queue_high=args.adaptive_queue_high,
                queue_low=args.adaptive_queue_low)
            base = controller.plan
            print(f"[serve] adaptive MP: tau ladder "
                  f"{[f'{t:g}' for t in controller.taus]} (base plan "
                  f"quantizes {base.n_quantized} ops, "
                  f"tier {base.meta.get('gain_tier')})")
        else:
            plan = _solve_from_bundle(bundle, args, src)
    elif args.mp_plan:
        plan = MPPlan.load(args.mp_plan)
        print(f"[serve] MP plan: {plan.n_quantized} ops quantized "
              f"(objective {plan.objective}, tau {plan.tau})")
        unknown = _plan_unknown_ops(model, params, plan)
        if unknown:
            print(f"[serve] WARNING: {len(unknown)} plan ops not in this "
                  f"model (e.g. {sorted(unknown)[:3]}) — they will NOT "
                  f"apply; was the plan solved for a different arch?")

    if args.mesh and not args.continuous:
        raise SystemExit("--mesh shards the continuous-batching engine; "
                         "pass --continuous")

    if args.continuous:
        from repro.launch.mesh import mesh_from_spec
        mesh = mesh_from_spec(args.mesh)
        if mesh is not None:
            print(f"[serve] mesh: {dict(mesh.shape)}")
        max_len = args.prompt_len + args.new_tokens
        n_blocks = args.n_blocks
        if n_blocks == "auto":
            from repro.serve.cache_pool import PagedCachePool
            if args.dense_slots:
                raise SystemExit("--n-blocks auto sizes the paged pool; "
                                 "drop --dense-slots")
            data_shards = mesh.shape["data"] if mesh is not None else 1
            profile = [(args.prompt_len, args.new_tokens)] * args.requests
            n_blocks = PagedCachePool.size_n_blocks(
                profile, args.n_slots, args.block_size,
                data_shards=data_shards)
            worst, _, _ = PagedCachePool.plan_blocks(
                args.n_slots, max_len, args.block_size,
                data_shards=data_shards)
            print(f"[serve] auto-sized paged pool: {n_blocks} blocks "
                  f"(worst case {worst}) from {args.requests}-request "
                  f"profile at p95 live demand x1.25 headroom")
        elif n_blocks is not None:
            n_blocks = int(n_blocks)
        injector = None
        if args.inject_faults:
            from repro.serve import FaultInjector
            injector = FaultInjector.parse(args.inject_faults)
            print(f"[serve] fault injection: {len(injector.specs)} scheduled "
                  f"({args.inject_faults})")
        guardrail = None
        if args.guardrail_every:
            from repro.serve import NumericalGuardrail
            guardrail = NumericalGuardrail(every=args.guardrail_every,
                                           margin=args.guardrail_margin)
            print(f"[serve] guardrail: shadow step every "
                  f"{args.guardrail_every} decode steps, breach at "
                  f"{args.guardrail_margin:g}x the plan's loss-MSE budget")
        eng = ContinuousBatchingEngine(model, n_slots=args.n_slots,
                                       max_len=max_len, mp=plan,
                                       paged=not args.dense_slots,
                                       block_size=args.block_size,
                                       n_blocks=n_blocks,
                                       chunk_len=args.chunk_len,
                                       chunk_budget=args.chunk_budget,
                                       paged_attn=args.paged_attn,
                                       mesh=mesh,
                                       prefix_cache=(False
                                                     if args.no_prefix_cache
                                                     else None),
                                       adaptive=controller,
                                       faults=injector,
                                       max_retries=args.max_retries,
                                       guardrail=guardrail)
        rng = np.random.default_rng(1)
        reqs = [Request(rid=i,
                        tokens=rng.integers(0, model.cfg.vocab_size,
                                            args.prompt_len).astype(np.int32),
                        max_new_tokens=args.new_tokens,
                        arrival=i * args.arrival_every)
                for i in range(args.requests)]
        # compile warm-up must not consume the fault schedule or trip
        # the guardrail's one-shot breach state
        eng.faults, eng.guardrail = None, None
        eng.serve(params, reqs[:1], sync=args.sync_engine)  # compile
        eng.faults, eng.guardrail = injector, guardrail
        out = eng.serve(params, reqs, sync=args.sync_engine)
        ttfts = sorted(r.ttft_s for r in out.results.values())
        p50 = f"{ttfts[len(ttfts)//2]*1e3:.2f} ms" if ttfts else "n/a"
        print(f"[serve] continuous: {args.requests} reqs via {args.n_slots} "
              f"slots | {out.n_steps} decode steps | "
              f"{out.tokens_per_s:.1f} tok/s | TTFT p50 {p50}")
        c = out.counters
        mode = "sync (lockstep)" if c["sync"] else "pipelined"
        print(f"[serve] host/device overlap [{mode}]: "
              f"{c['host_blocked_s_per_step'] * 1e6:.1f} us/step host-blocked "
              f"| {c['n_readbacks']} readbacks (batch mean "
              f"{c['readback_batch_mean']:.1f}, max {c['readback_batch_max']})"
              f" | device ran {c['steps_in_flight_peak']} steps ahead at peak")
        if c.get("paged"):
            print(f"[serve] paged KV: block_size {c['block_size']} | "
                  f"{c['peak_blocks_in_use']}/{c['n_blocks'] - 1} blocks at "
                  f"peak | peak KV {c['peak_kv_bytes'] / 1e6:.2f} MB vs dense "
                  f"{c['dense_kv_bytes'] / 1e6:.2f} MB | "
                  f"{c['blocked_admissions']} blocked admissions")
            if out.n_steps:
                print(f"[serve] decode attention ({c['paged_attn']}): "
                      f"{c['decode_attn_bytes_read'] / max(out.n_steps, 1) / 1e6:.3f} "
                      f"MB/step KV read (fused model "
                      f"{c['decode_attn_bytes_fused_model'] / 1e6:.2f} MB vs "
                      f"gather {c['decode_attn_bytes_gather_model'] / 1e6:.2f}"
                      f" MB over the drain)")
        print(f"[serve] prefill: {c['prefill_chunks']} chunk steps | "
              f"{c['prefill_buckets']} compile buckets for "
              f"{c['distinct_prompt_lens']} prompt lengths | "
              f"{c['decode_stall_steps']} decode-stall chunk steps "
              f"(longest run {c['max_decode_stall_run']})")
        if c.get("prefix_cache"):
            print(f"[serve] prefix cache: {c['prefix_hit_requests']} hit "
                  f"requests | {c['prefix_hit_tokens']} prompt tokens "
                  f"skipped | {c['cow_forks']} COW forks | "
                  f"{c['preemptions']} preemptions")
        if "adaptive" in c:
            a = c["adaptive"]
            print(f"[serve] adaptive MP: {a['downshifts']} downshifts / "
                  f"{a['restores']} restores over taus {a['taus']} | "
                  f"final tau {a['final_tau']:g} (level {a['final_level']}) "
                  f"| swaps at steps "
                  f"{[s['step'] for s in a['swaps']] or 'none'}")
        f = c.get("faults")
        if f and (f["seen"] or f["injected"]):
            inj_desc = ", ".join(f"{k}x{v}" for k, v in
                                 sorted(f["injected"].items())) or "none"
            print(f"[serve] faults: injected {inj_desc} | "
                  f"{f['contained']} contained / {f['retries']} retries / "
                  f"{f['failed']} failed | "
                  f"{f['quarantined_blocks']} blocks quarantined | "
                  f"kernel faults {f['kernel_faults']}"
                  + (" | degraded fused->gather"
                     if f["degraded_paged_attn"] else ""))
        g = c.get("guardrail")
        if g:
            print(f"[serve] guardrail: {g['checks']} shadow checks | "
                  f"{g['breaches']} breaches | last MSE "
                  f"{g['last_mse'] if g['last_mse'] is not None else 'n/a'}"
                  + (f" | restored base plan at step {g['restored_at']}"
                     if g["restored_at"] is not None else ""))
        n_failed = sum(1 for r in out.results.values()
                       if r.status == "failed")
        n_retried = sum(1 for r in out.results.values()
                        if r.status == "retried")
        if n_failed or n_retried:
            print(f"[serve] degraded results: {n_retried} retried "
                  f"(bit-identical after re-prefill) | {n_failed} failed "
                  f"(partial tokens returned)")
    else:
        eng = ServeEngine(model, mp=plan, donate=False)
        prompt = {"tokens": jax.random.randint(jax.random.key(1),
                                               (args.batch, args.prompt_len), 0,
                                               model.cfg.vocab_size)}
        eng.generate(params, dict(prompt), max_new_tokens=2)  # compile
        out = eng.generate(params, dict(prompt), max_new_tokens=args.new_tokens)
        print(f"[serve] TTFT {out.ttft_s*1e3:.2f} ms | "
              f"decode {out.tokens_per_s:.1f} tok/s | "
              f"batch {args.batch} x {args.new_tokens} new tokens")


if __name__ == "__main__":
    main()
