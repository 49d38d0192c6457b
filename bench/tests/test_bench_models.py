"""The served model is built from a published ``config.json`` by its
family's module (``bench/models/<model_type>.py``): the same model as
before for the benchmark's files, keys it cannot serve refused by name, and
the file's RMSNorm epsilon honoured, at tiny widths on the CPU."""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, weights
from bench.tests import tiny

_QWEN2P5_3B = dict(n_layers=36, d_model=2048, vocab_size=151936, n_heads=16,
                   n_kv_heads=2, d_head=128, d_ff=11008, qkv_bias=True,
                   activation="swiglu", norm="rmsnorm", rope_theta=1e6,
                   tie_embeddings=True)
# what the harness's own mapping gave these files before the mapping moved
# into the family module
EXPECTED = {
    "qwen2p5_3b": dict(_QWEN2P5_3B, name="qwen2p5_3b"),
    "qwen2p5_3b_amp": dict(_QWEN2P5_3B, name="qwen2p5_3b_amp"),
    "tiny": dict(name="tiny", n_layers=2, d_model=64, vocab_size=512,
                 n_heads=4, n_kv_heads=2, d_head=16, d_ff=128, qkv_bias=True,
                 activation="swiglu", norm="rmsnorm", rope_theta=1e6,
                 tie_embeddings=True),
}


def config_file(name: str) -> dict:
    if name == "tiny":
        return tiny.config()
    return harness.load_json(harness.BENCH / "configs" / f"{name}.json")


def reference():
    return harness.load_module(harness.BENCH / "configs"
                               / "qwen2_reference.py")


def program_has_norm_eps() -> bool:
    from repro.models.lm import LMConfig
    return "norm_eps" in {f.name for f in dataclasses.fields(LMConfig)}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_lm_config_is_the_model_served_before(name):
    """The fields the harness set, and every other field at the program's
    default (a ``norm_eps`` too: these files state the default, 1e-6)."""
    from repro.models.lm import LMConfig
    got = harness.lm_config(name, config_file(name))
    want = EXPECTED[name]
    assert {k: getattr(got, k) for k in want} == want
    default = LMConfig(name=name, n_layers=got.n_layers,
                       d_model=got.d_model, vocab_size=got.vocab_size)
    rest = [f.name for f in dataclasses.fields(LMConfig)
            if f.name not in want]
    assert {k: getattr(got, k) for k in rest} == \
        {k: getattr(default, k) for k in rest}


@pytest.mark.parametrize("key,value", [
    ("use_sliding_window", True), ("hidden_act", "gelu"),
    ("model_type", "no_such_family"), ("model_type", "../qwen2")])
def test_unserved_keys_are_refused_by_name(key, value):
    cfg = dict(tiny.config(), **{key: value})
    with pytest.raises(ValueError, match=key):
        harness.lm_config("tiny", cfg)


def test_rms_norm_eps_goes_to_a_program_that_takes_it(monkeypatch):
    """Where the program's ``LMConfig`` has a ``norm_eps`` field, the
    file's ``rms_norm_eps`` fills it; where it has none, only the epsilon
    the program uses (1e-6) is served, and any other is refused by name."""
    import repro.models.lm as lm
    cfg = dict(tiny.config(), rms_norm_eps=1e-5)
    if not program_has_norm_eps():
        with pytest.raises(ValueError, match="rms_norm_eps"):
            harness.lm_config("tiny", cfg)
        with_eps = dataclasses.make_dataclass(
            "LMConfig", [("norm_eps", float, 1e-6)], bases=(lm.LMConfig,),
            frozen=True)
        monkeypatch.setattr(lm, "LMConfig", with_eps)
    assert harness.lm_config("tiny", cfg).norm_eps == 1e-5


# Embedding rms of the parity test: the first layer's input then has a mean
# square of 9e-6, the order of the epsilon, and serving at 1e-6 against a
# reference at 1e-5 moves the logits by 0.70 of the largest of them. At the
# benchmark's embedding rms (0.05) it moves them by 7e-3 of the largest,
# which the bf16 cells' own rounding would hide.
EPS_SCALE_RMS = 3e-3
# Both sides compute in float32 on the CPU, the reference at HIGHEST
# precision; they differ only in the order of their sums: 5e-7 of the
# largest logit at these widths. 1e-4 leaves room for that, and the wrong
# epsilon misses it by four orders of magnitude.
TOLERANCE = 1e-4


def served_and_reference_logits(cfg: dict, ref_eps: float) -> tuple:
    """Logits of one 48-token prompt at every position: the program's model
    of ``cfg`` in float32, and the reference at ``ref_eps``, on the same
    seeded float32 weights with the embedding scaled to ``EPS_SCALE_RMS``."""
    from repro.models.registry import build_model
    from repro.quant.qops import QuantContext
    ref = reference()
    layout = {p: (shape, "float32", kind)
              for p, (shape, _, kind) in ref.param_layout(cfg).items()}
    params = weights.make_weights(layout, 5)
    params["embed"]["w"] = params["embed"]["w"] * (EPS_SCALE_RMS
                                                   / weights.EMBED_STD)
    model = build_model(dataclasses.replace(harness.lm_config("tiny", cfg),
                                            dtype="float32"))
    ids = np.random.default_rng(3).integers(0, cfg["vocab_size"], 48,
                                            dtype=np.int32)
    served = model.apply(params, jnp.asarray(ids)[None], QuantContext())[0]
    want = ref.logits_at(params, dict(cfg, rms_norm_eps=ref_eps), ids,
                         np.arange(len(ids)), len(ids))
    return np.asarray(served, np.float32), np.asarray(want)


def relative_gap(served, want) -> float:
    return float(np.max(np.abs(served - want)) / np.max(np.abs(want)))


@pytest.mark.parametrize("eps", [1e-6, 1e-5])
def test_served_model_matches_the_reference_at_its_epsilon(eps):
    """The model served from a file of epsilon ``eps`` matches the
    reference at ``eps`` and misses it at the other epsilon, so the test
    sees which one is served. A program that cannot serve ``eps`` refuses
    the file by name."""
    cfg = dict(tiny.config(), rms_norm_eps=eps)
    other = 1e-5 if eps == 1e-6 else 1e-6
    if eps != 1e-6 and not program_has_norm_eps():
        with pytest.raises(ValueError, match="rms_norm_eps"):
            harness.lm_config("tiny", cfg)
        # the served 1e-6 model lies outside the tolerance of a 1e-5 file
        served, want = served_and_reference_logits(
            dict(cfg, rms_norm_eps=1e-6), eps)
        assert relative_gap(served, want) > 100 * TOLERANCE
        return
    served, want = served_and_reference_logits(cfg, eps)
    assert relative_gap(served, want) < TOLERANCE
    served, want = served_and_reference_logits(cfg, other)
    assert relative_gap(served, want) > 100 * TOLERANCE


@pytest.mark.parametrize("eps", [1e-6, 1e-5])
def test_untied_file_builds_through_the_served_path(eps):
    """A file copied from ``qwen2p5_3b.json`` with an untied head (and
    ``rms_norm_eps`` 1e-5, where the program takes it) builds through the
    harness's model and layout path: the reference's layout, an output head
    of its own among them, is the served model's."""
    cfg = dict(tiny.config(), tie_word_embeddings=False, rms_norm_eps=eps)
    if eps != 1e-6 and not program_has_norm_eps():
        with pytest.raises(ValueError, match="rms_norm_eps"):
            harness.served_model("tiny", cfg, reference())
        cfg["rms_norm_eps"] = 1e-6
    model, params = harness.served_model("tiny", cfg, reference())
    assert model.cfg.tie_embeddings is False
    assert params["lm_head"]["w"].shape == (cfg["vocab_size"],
                                            cfg["hidden_size"])
