"""End-to-end AMP pipeline (Alg. 1) + baselines + serving consistency."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.baselines import prefix_strategy, random_strategy
from repro.core.pipeline import AMPOptions, auto_mixed_precision, predicted_loss_mse
from repro.core.sensitivity import calibrate_sensitivity
from repro.models.registry import get_model
from repro.quant.qops import QuantContext


@pytest.fixture(scope="module")
def setup():
    m = get_model("llama3_1b", smoke=True)
    params = m.init(jax.random.key(0))
    batches = [{"tokens": jax.random.randint(jax.random.key(i), (2, 32), 0, 512),
                "labels": jax.random.randint(jax.random.key(i + 50), (2, 32), 0, 512)}
               for i in range(3)]
    sens = calibrate_sensitivity(lambda p, b, c: m.loss(p, b, c), params,
                                 batches)
    return m, params, batches, sens


@pytest.mark.parametrize("objective", ["ET", "TT", "M"])
def test_pipeline_objectives(setup, objective):
    m, params, batches, sens = setup
    opts = AMPOptions(tau=0.02, objective=objective)
    plan = auto_mixed_precision(m, params, batches, opts, sens=sens)
    assert plan.predicted_loss_mse <= plan.budget * (1 + 1e-9)
    assert plan.predicted_gain >= 0
    assert plan.n_quantized > 0
    if objective == "M":
        # memory objective quantizes linear layers only (Sec. 2.3.3)
        assert all(("matmul" not in n) for n in plan.assignment)
    # predicted mse from the assignment equals the solver's d_total
    assert np.isclose(predicted_loss_mse(sens, plan.assignment),
                      plan.predicted_loss_mse, rtol=1e-6, atol=1e-12)


def test_predicted_loss_mse_additive_over_disjoint_assignments(setup):
    """Eq. (6)/(23): the loss MSE of a union of disjoint assignments is the
    sum of the parts (the additivity the IP decomposition relies on)."""
    m, params, batches, sens = setup
    names = sorted(op.name for op in sens.ops)
    assert len(names) >= 9
    a1 = {n: "fp8_e4m3" for n in names[0:3]}
    a2 = {n: "fp8_e5m2" for n in names[3:6]}
    a3 = {n: "fp8_e4m3" for n in names[6:9]}
    parts = (predicted_loss_mse(sens, a1) + predicted_loss_mse(sens, a2)
             + predicted_loss_mse(sens, a3))
    merged = predicted_loss_mse(sens, {**a1, **a2, **a3})
    assert np.isclose(merged, parts, rtol=1e-12)
    assert predicted_loss_mse(sens, {}) == 0.0
    # reference-format entries contribute exactly zero
    assert predicted_loss_mse(sens, {names[0]: "bf16"}) == 0.0
    assert np.isclose(
        predicted_loss_mse(sens, {**a1, names[3]: "bf16"}),
        predicted_loss_mse(sens, a1), rtol=1e-12)
    # unknown op names fall back to zero sensitivity rather than crashing
    assert predicted_loss_mse(sens, {"ghost_op": "fp8_e4m3"}) == 0.0


def test_gain_monotone_in_tau(setup):
    m, params, batches, sens = setup
    gains = []
    for tau in (0.001, 0.01, 0.05):
        plan = auto_mixed_precision(
            m, params, batches, AMPOptions(tau=tau, objective="TT"), sens=sens)
        gains.append(plan.predicted_gain)
    assert gains[0] <= gains[1] <= gains[2]


def test_ip_beats_baselines(setup):
    """At equal budget, IP-TT gain >= Random/Prefix gain (optimality)."""
    from repro.core.timegain import TheoreticalGainModel
    from repro.hw.profiles import TPU_V5E
    m, params, batches, sens = setup
    opts = AMPOptions(tau=0.01, objective="TT")
    plan = auto_mixed_precision(m, params, batches, opts, sens=sens)
    budget = plan.budget
    names = [op.name for op in sens.ops]
    gm = TheoreticalGainModel(TPU_V5E)
    op_index = {op.name: op for op in sens.ops}

    def gain_of(assignment):
        return sum(gm.op_gain(op_index[n], f) for n, f in assignment.items())

    rnd = random_strategy(names, sens, budget, seed=3)
    pfx = prefix_strategy(names, sens, budget)
    assert plan.predicted_gain >= gain_of(rnd) - 1e-12
    assert plan.predicted_gain >= gain_of(pfx) - 1e-12
    # baselines respect the budget
    assert predicted_loss_mse(sens, rnd) <= budget * (1 + 1e-9)
    assert predicted_loss_mse(sens, pfx) <= budget * (1 + 1e-9)


def test_mp_serving_consistency(setup):
    """Prefill/decode under the MP plan stays close to bf16 serving."""
    m, params, batches, sens = setup
    plan = auto_mixed_precision(m, params, batches,
                                AMPOptions(tau=0.01, objective="TT"),
                                sens=sens)
    toks = batches[0]["tokens"][:, :16]
    ctx_mp = QuantContext(mode="mp", mp=plan.assignment)
    caches = m.init_cache(2, 20)
    lp_mp, caches = m.prefill(params, toks, caches, ctx_mp)
    caches2 = m.init_cache(2, 20)
    lp, caches2 = m.prefill(params, toks, caches2, QuantContext())
    a = np.asarray(lp_mp, np.float32)
    b = np.asarray(lp, np.float32)
    # logits deviate only mildly under the loss-MSE-constrained plan
    # (random-init logits are near zero, so the relative scale is generous)
    assert np.max(np.abs(a - b)) / (np.abs(b).max() + 1e-6) < 0.4


def test_wallclock_gain_model_additivity_interface(setup):
    """WallClockGainModel measures per-group deltas through the engine."""
    import time
    from repro.core.timegain import WallClockGainModel
    m, params, batches, sens = setup
    toks = batches[0]["tokens"][:, :16]

    def factory(assignment):
        ctx = QuantContext(mode="mp", mp=assignment) if assignment else QuantContext()
        fn = jax.jit(lambda p, t: m.apply(p, t, ctx))

        def run():
            jax.block_until_ready(fn(params, toks))
        return run

    gm = WallClockGainModel(run_factory=factory, n_iters=2, n_warmup=1)
    ops = sens.ops[:2]
    combos = [("bf16", "bf16"), ("fp8_e4m3", "fp8_e4m3")]
    gains = gm.gains(ops, combos)
    assert gains.shape == (2,)
    assert gains[0] == 0.0  # all-ref combo is zero by definition


def test_calibrate_op_chunk_matches_one_pass(setup):
    """``op_chunk`` only splits which ops carry probes per backward pass:
    each op's sensitivity is the same as from one pass over all ops."""
    from repro.core.pipeline import calibrate
    m, params, batches, sens = setup
    bundle = calibrate(m, params, batches[:1], gain_models={}, op_chunk=3)
    one = calibrate_sensitivity(lambda p, b, c: m.loss(p, b, c), params,
                                batches[:1])
    assert len(sens.ops) > 3            # several chunks
    assert bundle.sens.sensitivity.keys() == one.sensitivity.keys()
    for name, s in one.sensitivity.items():
        np.testing.assert_allclose(bundle.sens.sensitivity[name], s,
                                   rtol=1e-5, err_msg=name)


@pytest.mark.parametrize("kind", ["TPU v5 lite", "TPU v5e"])
def test_profile_for_device_kind_is_the_default_profile(kind):
    from repro.hw.profiles import TPU_V5E, profile_for_device_kind
    assert profile_for_device_kind(kind) is TPU_V5E is AMPOptions().hw
    # published v5e peaks are not marked modeled; fp8 rows are
    assert {"bf16", "int8"}.isdisjoint(TPU_V5E.modeled)
    assert "fp8_e4m3" in TPU_V5E.modeled


def test_profile_for_unknown_device_kind_is_an_error():
    from repro.hw.profiles import profile_for_device_kind
    with pytest.raises(LookupError, match="no hardware profile"):
        profile_for_device_kind("cpu")
