"""The hybrid (Zamba2) configuration at a tiny size on the CPU: the program
against the plain reference (``reference_hybrid.py``) on seeded weights, the
reference against the ``transformers`` library's Zamba2, and a whole train
run of the hybrid driver.

Both the program and the reference compute in float32 here; they agree to
float32 rounding: at most 5e-6 of each leaf's largest gradient entry at this
size (about 4e-6 read on the seed below), which the tolerances leave room
for, and a gradient computed at bfloat16 would miss by orders of magnitude.
"""
import copy
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference
import reference_hybrid as rh
import spec
import weights_hybrid as wh
from traffic import TokenFeed

from repro.models.lm import build_model

hybrid = spec.driver("train_hybrid")
SEED = 2 ** 33 + 15
# Every width cut, the same structure: five Mamba-2 layers, the shared block
# invoked at layers 2 and 4 with its adapters on, a tied embedding.
TINY_HYBRID = {
    "name": "tiny-hybrid", "registry": "zamba2-1.2b",
    "d_model": 64, "n_layers": 5, "vocab_size": 500, "padded_vocab": 512,
    "tie_embeddings": True, "norm_eps": 1e-5,
    "ssm": {"d_state": 16, "d_conv": 4, "expand": 2, "head_dim": 32,
            "chunk": 32, "n_groups": 1},
    "shared": {"every": 2, "attn_in": 128, "n_heads": 4, "head_dim": 32,
               "attn_scale": 0.25, "d_ff": 128, "adapter_rank": 8,
               "attn_adapters": True, "rope_theta": 10000.0},
    "reduced": ["n_layers", "d_model", "padded_vocab", "ssm", "shared"],
    "run": {"param_dtype": "float32", "compute_dtype": "float32",
            "ssd_impl": "pallas", "attn_impl": "blocked", "remat": "full"},
    "optimizer": {"name": "adamw", "lr": 3e-4, "b1": 0.9, "b2": 0.95,
                  "eps": 1e-8, "weight_decay": 0.1, "grad_clip": 1.0},
}
TINY_TRAFFIC = {"name": "tiny", "kind": "train_hybrid", "batch": 2,
                "seq": 64, "mix": {"ramp": 1, "markov": 1}, "check_steps": 3,
                "trace_steps": 2}
GRAD_TOL = 2e-5
LOUD = 50.0


@pytest.fixture(scope="module")
def tiny():
    cfg = copy.deepcopy(TINY_HYBRID)
    params = wh.make_fn(cfg)(*wh.seed_args(SEED))
    tokens = jnp.asarray(np.random.default_rng(SEED).integers(
        0, cfg["vocab_size"], (2, 64)), jnp.int32)
    return cfg, params, tokens


def _model(cfg, **kw):
    return build_model(hybrid.program_config(cfg), ssd_impl="pallas",
                       kv_block=32, **kw)


def _ref_grad(cfg, params, tokens):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(rh.loss)(params, tokens, cfg)


@pytest.fixture(scope="module")
def ref_grad(tiny):
    return _ref_grad(*tiny)


def _rel(a, b):
    return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))


def test_program_loss_and_every_gradient_leaf_match_the_reference(tiny,
                                                                  ref_grad):
    cfg, params, tokens = tiny
    model = _model(cfg)
    assert model.cfg.hybrid_layer_ids == (2, 4)
    loss, grad = jax.value_and_grad(
        lambda p: model.loss_fn(p, {"tokens": tokens})[0])(params)
    ref_loss, ref = ref_grad
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-6)
    flat = dict((jax.tree_util.keystr(k), v) for k, v in
                jax.tree_util.tree_flatten_with_path(grad)[0])
    want = dict((jax.tree_util.keystr(k), v) for k, v in
                jax.tree_util.tree_flatten_with_path(ref)[0])
    assert flat.keys() == want.keys()
    for name, g in want.items():
        assert float(jnp.max(jnp.abs(g))) > 0, name
        if name.startswith(("['layers']", "['invocations']")):
            # stacked leaves slice by slice: each layer, each invocation
            for i in range(g.shape[0]):
                assert _rel(flat[name][i], g[i]) < GRAD_TOL, (name, i)
        else:
            assert _rel(flat[name], g) < GRAD_TOL, name
    # both invocations' adapters and linears have a gradient of their own
    assert want["['invocations']['linear']"].shape[0] == 2
    assert all(float(jnp.max(jnp.abs(a))) > 0
               for a in want["['invocations']['q']['b']"])


def test_shared_gradient_sums_its_invocations(tiny, ref_grad):
    """With one invocation's output cut off, the shared block's gradient
    loses that invocation's part: the sum over invocations is what the
    program and the reference both give."""
    cfg, params, tokens = tiny
    cut = dict(params, invocations=dict(
        params["invocations"],
        linear=params["invocations"]["linear"].at[1].set(0.0)))
    _, one = _ref_grad(cfg, cut, tokens)
    full = ref_grad[1]["shared"]["attn"]["wq"]
    assert _rel(one["shared"]["attn"]["wq"], full) > 100 * GRAD_TOL


def test_embedding_gradient_takes_the_path_through_e(tiny, monkeypatch):
    """e's part of the embedding gradient (the reference's gradient less
    that of the reference without e's path into the shared block) is the
    program's too.  The linears are scaled up so that the block's output
    weighs in the stream: that part is then 2e-4 of the gradient, against a
    program-reference gap of 1e-6."""
    cfg, params, tokens = tiny
    params = dict(params, invocations=dict(
        params["invocations"],
        linear=params["invocations"]["linear"] * LOUD))
    model = _model(cfg)
    grad = jax.grad(lambda p: model.loss_fn(p, {"tokens": tokens})[0])(params)
    _, ref = _ref_grad(cfg, params, tokens)
    block = rh.shared_block
    monkeypatch.setattr(rh, "shared_block", lambda sp, ip, h, e, cfg, mm:
                        block(sp, ip, h, jax.lax.stop_gradient(e), cfg, mm))
    _, without = _ref_grad(cfg, params, tokens)
    table, rest = ref["embed"]["table"], without["embed"]["table"]
    assert _rel(grad["embed"]["table"], table) < GRAD_TOL
    assert _rel(grad["embed"]["table"] - rest, table - rest) < 0.02


def test_prefill_then_decode_match_the_reference_forward(tiny):
    cfg, params, tokens = tiny
    model = _model(cfg, attn_impl="naive")
    tokens = tokens[:1]
    with jax.default_matmul_precision("highest"):
        full = rh.logits(params, tokens, cfg)
    logits, cache = model.prefill_fn(params, {"tokens": tokens[:, :56]})
    cache = dict(cache, **{k: jnp.pad(cache[k], ((0, 0), (0, 0), (0, 8),
                                                 (0, 0), (0, 0)))
                           for k in ("shared_k", "shared_v")})
    assert cache["shared_k"].shape == (2, 1, 64, 4, 32)
    errs = [_rel(logits, full[:, 55])]
    for pos in range(56, 63):
        logits, cache = model.decode_fn(params, cache, {
            "tokens": tokens[:, pos:pos + 1],
            "pos": jnp.asarray(pos, jnp.int32)})
        errs.append(_rel(logits, full[:, pos]))
    assert max(errs) < 1e-4, errs


def _to_torch(cfg, params):
    """The same weights as ``transformers``' Zamba2ForCausalLM state."""
    import torch

    sh, ids = cfg["shared"], rh.hybrid_ids(cfg)
    a, r, d = sh["attn_in"], sh["adapter_rank"], cfg["d_model"]
    p = jax.tree.map(lambda x: torch.tensor(np.asarray(x)), params)
    L, sp, inv = p["layers"], p["shared"], p["invocations"]

    def lin(w):                     # (in, ...) -> torch's (out, in)
        return w.reshape(w.shape[0], -1).T

    state = {"model.embed_tokens.weight": p["embed"]["table"],
             "model.final_layernorm.weight": p["final_norm"]["scale"],
             "lm_head.weight": p["embed"]["table"]}
    for i in range(cfg["n_layers"]):
        m = {k: v[i] for k, v in L["mamba"].items()}
        pre = f"model.layers.{i}." + ("mamba_decoder." if i in ids else "")
        state.update({
            pre + "input_layernorm.weight": L["ln"]["scale"][i],
            pre + "mamba.in_proj.weight": torch.cat(
                [m["wz"], m["wx"], m["wB"], m["wC"], m["wdt"]], 1).T,
            pre + "mamba.conv1d.weight": torch.cat(
                [m["conv_x_w"], m["conv_B_w"], m["conv_C_w"]])[:, None],
            pre + "mamba.conv1d.bias": torch.cat(
                [m["conv_x_b"], m["conv_B_b"], m["conv_C_b"]]),
            pre + "mamba.dt_bias": m["dt_bias"],
            pre + "mamba.A_log": m["A_log"], pre + "mamba.D": m["D"],
            pre + "mamba.norm.weight": m["norm"],
            pre + "mamba.out_proj.weight": m["out_proj"].T})
    for i in ids:
        pre = f"model.layers.{i}."
        st = pre + "shared_transformer."
        state[st + "input_layernorm.weight"] = sp["ln_in"]["scale"]
        state[st + "pre_ff_layernorm.weight"] = sp["ln_ff"]["scale"]
        for w in ("q", "k", "v"):
            state[st + f"self_attn.{w}_proj.weight"] = lin(sp["attn"]["w" + w])
        state[st + "self_attn.o_proj.weight"] = sp["attn"]["wo"].reshape(
            a, d).T
        state[st + "feed_forward.gate_up_proj.weight"] = lin(
            sp["mlp"]["gate_up"])
        state[st + "feed_forward.down_proj.weight"] = sp["mlp"]["down"].T
        # the block is one module under every hybrid layer: each holds the
        # adapters of all invocations
        for j, i_j in enumerate(ids):
            if i_j == i:
                state[pre + "linear.weight"] = inv["linear"][j].T
            for w in ("q", "k", "v"):
                ad = st + f"self_attn.linear_{w}_adapter_list.{j}."
                state[ad + "0.weight"] = inv[w]["a"][j].T
                state[ad + "1.weight"] = inv[w]["b"][j].reshape(r, a).T
            ad = st + f"feed_forward.gate_up_proj_adapter_list.{j}."
            state[ad + "0.weight"] = inv["gate_up"]["a"][j].T
            state[ad + "1.weight"] = inv["gate_up"]["b"][j].reshape(r, -1).T
    return state


def test_reference_logits_match_transformers_zamba2(tiny):
    """transformers' Zamba2 (its plain torch path) on the same weights.
    The mixers' ``time_step_min`` is set to 0: that path clamps dt from
    below at it, which Zyphra's fused kernel path (and the program) does
    not.  Its chunk spans the whole sequence: between chunks that path
    (transformers 4.57) sums the chunk decay over the wrong axis, where its
    Mamba2 and Bamba transpose it first.  The reference keeps its own
    chunk, so its inter-chunk path is checked too."""
    torch = pytest.importorskip("torch")
    tf = pytest.importorskip("transformers")
    cfg, params, tokens = tiny
    sh, L = cfg["shared"], cfg["n_layers"]
    ids = rh.hybrid_ids(cfg)
    conf = tf.Zamba2Config(
        vocab_size=cfg["padded_vocab"], hidden_size=cfg["d_model"],
        num_hidden_layers=L,
        layers_block_type=["hybrid" if i in ids else "mamba"
                           for i in range(L)],
        mamba_d_state=cfg["ssm"]["d_state"], mamba_d_conv=4, mamba_expand=2,
        mamba_ngroups=1,
        n_mamba_heads=2 * cfg["d_model"] // cfg["ssm"]["head_dim"],
        chunk_size=tokens.shape[1], use_mem_eff_path=False,
        intermediate_size=sh["d_ff"], hidden_act="gelu",
        num_attention_heads=sh["n_heads"], num_key_value_heads=sh["n_heads"],
        num_mem_blocks=1, use_shared_attention_adapter=True,
        adapter_rank=sh["adapter_rank"], use_mem_rope=True,
        rope_theta=sh["rope_theta"], rms_norm_eps=cfg["norm_eps"],
        tie_word_embeddings=True, use_cache=False,
        attn_implementation="eager")
    assert conf.attention_hidden_size == sh["attn_in"]
    assert conf.attention_head_dim == sh["head_dim"]
    assert (conf.attention_head_dim / 2) ** -0.5 == sh["attn_scale"]
    model = tf.Zamba2ForCausalLM(conf).eval()
    for m in model.modules():
        if hasattr(m, "time_step_min"):
            m.time_step_min = 0.0
    missing, unexpected = model.load_state_dict(_to_torch(cfg, params),
                                                strict=False)
    assert not unexpected and not [k for k in missing
                                   if "rotary_emb" not in k], missing
    with torch.no_grad():
        got = model(torch.tensor(np.asarray(tokens)), use_cache=False,
                    logits_to_keep=0).logits.numpy()
    with jax.default_matmul_precision("highest"):
        want = np.asarray(rh.logits(params, tokens, cfg))
    assert np.max(np.abs(got - want)) / np.max(np.abs(want)) < 1e-5


def test_hybrid_cell_program_is_correct_and_control_is_not(tmp_path):
    cfg, traffic = copy.deepcopy(TINY_HYBRID), copy.deepcopy(TINY_TRAFFIC)
    limits = spec.limits("zamba2-1.2b.train-4k")
    res = hybrid.run(cfg, traffic, limits, seed=SEED, seconds=0.2,
                     trace=False, t0=time.perf_counter(), out_dir=tmp_path)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > traffic["check_steps"]
    tokens = TokenFeed.from_traffic(traffic, cfg["vocab_size"], SEED)
    ref = hybrid.reference_readings(cfg, traffic, SEED, tokens.batch)
    control = hybrid.reference_readings(cfg, traffic, SEED, tokens.batch,
                                        mm=reference.int8_mm)
    got = hybrid.gaps(control, ref)
    assert any(got[k] > limits[k] for k in limits), got
