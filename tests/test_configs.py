"""Config sanity: param counts vs published sizes, shape applicability."""
import pytest

from repro.configs import ARCHS, SHAPES, reduced_config, shapes_for, \
    skipped_shapes_for
from repro.models import params as pr
from repro.models.lm import build_model

# name -> (published params, tolerance).  Tolerances are loose where public
# configs are ambiguous (padded vocab, biases, exact d_ff).
PUBLISHED = {
    "paligemma-3b": (2.9e9, 0.25),       # 3B incl. vision tower (ours: stub)
    "zamba2-1.2b": (1.2e9, 0.02),        # 1.205B: see the test below
    "nemotron-4-340b": (340e9, 0.10),
    "qwen1.5-32b": (32e9, 0.10),
    "qwen1.5-110b": (110e9, 0.10),
    "chatglm3-6b": (6e9, 0.15),
    "mamba2-1.3b": (1.3e9, 0.10),
    "llama4-scout-17b-a16e": (109e9, 0.30),   # 17B active / 109B total
    "grok-1-314b": (314e9, 0.10),
    "whisper-large-v3": (1.5e9, 0.25),
}


@pytest.mark.parametrize("name", sorted(ARCHS))
def test_param_count_matches_published(name):
    cfg = ARCHS[name]
    n = cfg.param_count()
    target, tol = PUBLISHED[name]
    assert abs(n - target) / target < tol, \
        f"{name}: {n / 1e9:.2f}B vs published {target / 1e9:.1f}B"


@pytest.mark.parametrize("name", sorted(ARCHS))
def test_param_count_matches_built_tree(name):
    """param_count() (closed form) must equal the actual spec tree."""
    cfg = ARCHS[name]
    model = build_model(cfg)
    assert pr.count(model.param_specs()) == cfg.param_count()


@pytest.mark.parametrize("name", sorted(ARCHS))
def test_active_params(name):
    cfg = ARCHS[name]
    active = cfg.active_param_count()
    assert active <= cfg.param_count()
    if cfg.moe is None:
        assert active == cfg.param_count()
    else:
        assert active < cfg.param_count()


def test_zamba2_param_count_by_part():
    """Zamba2-1.2B as built: 38 Mamba-2 layers, the shared block counted
    once, and each of its 6 invocations' adapters and linear."""
    cfg = ARCHS["zamba2-1.2b"]
    mamba = (2048 * (2 * 4096 + 2 * 64 + 64)     # in projections
             + (4096 + 2 * 64) * 5                 # conv weight and bias
             + 3 * 64 + 4096 + 4096 * 2048 + 2048)  # dt, A, D; norms; out
    shared = (4096 + 3 * 4096 * 4096 + 4096 * 2048  # [h; e] norm, q/k/v, o
              + 2048 + 2048 * 2 * 8192 + 8192 * 2048)  # pre-MLP norm, MLP
    per_invocation = (3 * (4096 * 128 + 128 * 4096)  # q/k/v adapters
                      + 2048 * 128 + 128 * 2 * 8192  # gate/up adapter
                      + 2048 * 2048)                 # linear
    embed = 32000 * 2048 + 2048                      # tied; final norm
    assert cfg.hybrid_layer_ids == (6, 12, 18, 24, 30, 36)
    assert cfg.param_count() == embed + 38 * mamba + shared \
        + 6 * per_invocation == 1_205_078_912


def test_moe_actives_roughly_published():
    llama4 = ARCHS["llama4-scout-17b-a16e"]
    assert abs(llama4.active_param_count() - 17e9) / 17e9 < 0.35
    grok = ARCHS["grok-1-314b"]
    assert abs(grok.active_param_count() - 86e9) / 86e9 < 0.30


def test_shapes_accounting_40_cells():
    """10 archs x 4 shapes = 40 cells: 32 run + 8 documented skips."""
    run = sum(len(shapes_for(c)) for c in ARCHS.values())
    skipped = sum(len(skipped_shapes_for(c)) for c in ARCHS.values())
    assert run == 32
    assert skipped == 8
    assert run + skipped == len(ARCHS) * len(SHAPES)


def test_long_500k_only_subquadratic():
    for cfg in ARCHS.values():
        names = {s.name for s in shapes_for(cfg)}
        if cfg.family in ("ssm", "hybrid"):
            assert "long_500k" in names, cfg.name
        else:
            assert "long_500k" not in names, cfg.name


@pytest.mark.parametrize("name", sorted(ARCHS))
def test_reduced_config_preserves_structure(name):
    cfg = ARCHS[name]
    red = reduced_config(cfg)
    assert red.family == cfg.family
    assert (red.moe is None) == (cfg.moe is None)
    assert (red.ssm is None) == (cfg.ssm is None)
    assert bool(red.shared_attn_every) == bool(cfg.shared_attn_every)
    assert bool(red.n_encoder_layers) == bool(cfg.n_encoder_layers)
    assert red.qkv_bias == cfg.qkv_bias
    assert red.mlp_kind == cfg.mlp_kind
    assert red.rope_fraction == cfg.rope_fraction
    if cfg.n_heads:
        assert red.n_heads // red.n_kv_heads == \
            max(1, cfg.n_heads // cfg.n_kv_heads) or red.n_kv_heads == 1
    assert red.param_count() < 10e6


def test_padded_vocab_shards():
    for cfg in ARCHS.values():
        assert cfg.padded_vocab % 256 == 0
        assert cfg.padded_vocab >= cfg.vocab_size
