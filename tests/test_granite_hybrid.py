"""Granite 4.0-H through the program's normal path, against the plain
reference (``bench/models/granite_hybrid.py``) and the published code.

At a tiny size on the CPU: the forward with Pallas off and in interpret
mode; one request prefilled and decoded through ``decode_step``; the
held-expert MoE (dropless, shares adding up to the uncut layer, work
only on routed rows); the new config fields leaving the other models'
programs as they were; and the reference against ``transformers``'
``GraniteMoeHybridForCausalLM``.
"""

import hashlib
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.distributed.sharding import Sharder
from repro.kernels.moe_experts import moe_experts_pallas
from repro.models import Model
from repro.models import layers as L
from repro.models import transformer as T

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)
from bench import harness  # noqa: E402
from bench.models import granite_hybrid as ref  # noqa: E402

SH = Sharder(mesh=None)
F32 = jnp.float32

# Published widths cut to a CPU's size; a period of two layers (Mamba-2,
# then attention) repeated twice, 8 experts of which 4 are held here.
TINY = dict(num_hidden_layers=4, layer_types=["mamba", "attention"] * 20,
            hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
            head_dim=16, attention_multiplier=1 / 16, intermediate_size=32,
            shared_intermediate_size=48, num_local_experts=4,
            published={"num_hidden_layers": 40, "num_local_experts": 8},
            num_experts_per_tok=3, vocab_size=256, mamba_n_heads=8,
            mamba_d_head=16, mamba_d_state=16)


def _config(**over) -> dict:
    with open(os.path.join(REPO, "bench", "configs",
                           "granite-4_0-h-small.json")) as f:
        return dict(json.load(f), **dict(TINY, **over))


def _program(c: dict, dtype=F32, use_pallas=False):
    fields = ref.program_fields(c)
    # A property the harness checks; set through head size and expansion.
    assert fields.pop("mamba_heads") == c["mamba_n_heads"]
    period = 2
    fields["block_pattern"] = fields["block_pattern"][:period]
    return get_config("granite-4.0-h-small", smoke=True).replace(
        **fields, dtype=dtype, use_pallas=use_pallas)


def _weights(c, pcfg, seed=0):
    return harness.make_weights(c, ref, pcfg, seed)


def _tokens(c, rows, seq, seed=1):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, c["vocab_size"], (rows, seq)), jnp.int32)


def _program_logits(pcfg, w, tokens):
    x = T.embed_tokens(pcfg, w, tokens)
    hidden, _ = T.forward(pcfg, w, x, SH)
    return T.unembed(pcfg, w, hidden)


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.linalg.norm(got - want, axis=-1)
                        / np.linalg.norm(want, axis=-1)))


@pytest.mark.parametrize("use_pallas", [False, True])
def test_forward_matches_reference(use_pallas):
    c = _config()
    pcfg = _program(c, use_pallas=use_pallas)
    w = _weights(c, pcfg)
    tokens = _tokens(c, 2, 64)
    got = jax.jit(lambda w, t: _program_logits(pcfg, w, t))(w, tokens)
    pos = jnp.arange(64)
    want = jax.jit(lambda w, t: ref.reference_logits(c, w, t, pos))(
        w, tokens)
    assert got.shape[-1] == c["vocab_size"] == want.shape[-1]
    assert _rel(got, want) < 1e-5


def test_prefill_then_decode_matches_reference():
    """One request (batch 1): its prompt fed through ``decode_step`` one
    token at a time, as the engine prefills, then greedy decoding; every
    step's logits against the reference's full forward."""
    c = _config()
    pcfg = _program(c)
    w = _weights(c, pcfg, seed=3)
    prompt, new = 12, 8
    tokens = list(np.asarray(_tokens(c, 1, prompt, seed=4))[0])
    cache = Model(pcfg).init_cache(1, prompt + new)
    step = jax.jit(lambda w, t, pos, c_: T.decode_step(pcfg, w, t, pos, c_,
                                                       SH))
    got = []
    for t in range(prompt + new - 1):
        logits, cache = step(w, jnp.asarray([tokens[t]], jnp.int32),
                             jnp.asarray([t], jnp.int32), cache)
        got.append(np.asarray(logits[0]))
        if t >= prompt - 1:
            tokens.append(int(np.argmax(got[-1])))
    seq = jnp.asarray([tokens], jnp.int32)
    want = ref.reference_logits(c, w, seq, jnp.arange(len(tokens) - 1))[0]
    assert _rel(np.stack(got), want) < 1e-5


def _moe_inputs(c, pcfg, seed=5, tokens=48):
    w = _weights(c, pcfg, seed)
    p = jax.tree.map(lambda a: a[0], w["blocks"]["pos0"])
    h = jax.random.normal(jax.random.PRNGKey(seed), (2, tokens // 2,
                                                     c["hidden_size"]), F32)
    return p, h


def test_shares_add_up_to_the_uncut_layer():
    """8 experts in 4 shares of 2: each share's part of the MoE, summed,
    with the shared expert counted once, is the layer with all 8 held."""
    c = _config(num_local_experts=8)
    whole = _program(c)
    p, h = _moe_inputs(c, whole)
    y_whole, _ = L.moe(whole, p, h, SH)
    want = ref._moe(c, p, h, lambda a: a)
    assert _rel(y_whole + L.shared_expert(whole, p, h), want) < 1e-5

    share = whole.replace(experts_held=2)
    parts = []
    for s in range(4):
        ps = dict(p, **{k: p[k][2 * s:2 * s + 2]
                        for k in ("we_gate", "we_up", "we_down")})
        parts.append(L.moe(share, ps, h, SH, first_expert=2 * s)[0])
    assert _rel(sum(parts), y_whole) < 1e-5
    # No share alone is the layer.
    assert all(_rel(part, y_whole) > 0.1 for part in parts)


@pytest.mark.parametrize("use_pallas", [False, True])
def test_no_pair_dropped_when_every_token_picks_the_same_experts(
        use_pallas):
    """A router that sends every token to experts 0..k-1, all held: the
    buffer is full to its bound and every (token, expert) pair counts."""
    c = _config()
    pcfg = _program(c, use_pallas=use_pallas)
    p, h = _moe_inputs(c, pcfg, tokens=256)
    k = c["num_experts_per_tok"]
    h = jnp.abs(h) + 0.5
    router = jnp.full(p["router"].shape, -1.0, F32).at[:, :k].set(
        jnp.arange(1.0, k + 1.0) / c["hidden_size"] * 4)
    p = dict(p, router=router)
    got, _ = L.moe(pcfg, p, h, SH)
    want = ref._moe(c, p, h, lambda a: a) - L.shared_expert(pcfg, p, h)
    assert _rel(got, want) < 1e-5
    # The capacity-dropping path, with the same experts, drops pairs.
    dropping = pcfg.replace(experts_held=0, n_experts=4, moe_group=256)
    lossy, _ = L.moe(dropping, dict(p, router=router[:, :4]), h, SH)
    assert _rel(lossy, want) > 0.1


def test_expert_rows_past_the_routed_ones_are_never_computed():
    """The kernel's grid covers the routed rows' tiles, whatever the size
    of the buffer: rows past them are never read (NaN there changes
    nothing) and the tiles visited do not grow with the buffer."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import (
        make_group_metadata)

    rng = np.random.default_rng(0)
    m, k, n = 512, 128, 128
    sizes = jnp.asarray([40, 0, 25, 71], jnp.int32)
    routed = int(sizes.sum())
    lhs = jnp.asarray(rng.standard_normal((m, k)), F32)
    lhs = lhs.at[routed:].set(jnp.nan)
    rhs = jnp.asarray(rng.standard_normal((4, k, n)), F32)
    got = moe_experts_pallas(lhs, rhs, sizes, tm=32, tk=128, tn=128,
                             interpret=True)[:routed]
    want = jax.lax.ragged_dot(lhs[:routed], rhs, sizes,
                              precision=jax.lax.Precision.HIGHEST)
    assert bool(jnp.all(jnp.isfinite(got)))
    assert _rel(got, want) < 1e-5
    for buffer in (512, 8192, 294912):
        _, tiles = make_group_metadata(
            group_sizes=sizes, m=buffer, tm=32,
            start_group=jnp.zeros((), jnp.int32), num_nonzero_groups=4,
            visit_empty_groups=False)
        assert int(tiles) <= math.ceil(routed / 32) + 3


# Hashes of the smoke programs' StableHLO at the parent of the change that
# added the Granite fields (embedding, residual and logit multipliers,
# NoPE, the attention scale, the shared expert, experts held): each field
# at its default must leave these programs as they were.  Kernels launch
# at their static defaults, so no tuned driver in the process moves them.
# The internlm2 Pallas forward's hash is the one after flash attention
# began skipping its fully masked blocks, which alone changed that program.
# The decode hashes are the ones after the decode step began carrying its
# cache through the layer scan and writing each token's row in place,
# which alone changed those programs.
PROGRAM_HASHES = {
    ("internlm2-1.8b", False): ("b151dfdefd5b22c6", "12179f8db338cc80"),
    ("internlm2-1.8b", True): ("2dd2406521140e94", "12179f8db338cc80"),
    ("mamba2-130m", False): ("73505ce9472af452", "8b7fe6970c79e8ff"),
    ("mamba2-130m", True): ("43b419110dd4cfee", "8b7fe6970c79e8ff"),
    ("qwen3-moe-235b-a22b", False): ("7bc27acd781ad923", "d8b8527e8bce4a85"),
}


@pytest.mark.parametrize("arch,use_pallas", sorted(PROGRAM_HASHES))
def test_other_programs_unchanged(arch, use_pallas, monkeypatch):
    from repro.kernels import ops

    monkeypatch.setattr(ops, "choose_or_default",
                        lambda kernel, D, default: dict(default))
    cfg = get_config(arch, smoke=True).replace(use_pallas=use_pallas)
    params = Model(cfg).abstract_params()

    def forward(p, t):
        x = T.embed_tokens(cfg, p, t)
        hidden, aux = T.forward(cfg, p, x, SH)
        return T.unembed(cfg, p, hidden[:, -8:]), aux

    def decode(p, t, pos, cache):
        return T.decode_step(cfg, p, t, pos, cache, SH)

    ids = jax.ShapeDtypeStruct((2,), jnp.int32)
    cache = jax.eval_shape(lambda: Model(cfg).init_cache(2, 64))
    texts = (jax.jit(forward).lower(
                 params, jax.ShapeDtypeStruct((2, 256), jnp.int32)).as_text(),
             jax.jit(decode).lower(params, ids, ids, cache).as_text())
    got = tuple(hashlib.sha256(t.encode()).hexdigest()[:16] for t in texts)
    assert got == PROGRAM_HASHES[(arch, use_pallas)]


def test_published_sizes_and_the_chip_share():
    full = get_config("granite-4.0-h-small")
    kinds = [full.block_pattern[i % full.period].kind
             for i in range(full.n_layers)]
    assert [i for i, k in enumerate(kinds) if k == "attn"] == [5, 15, 25, 35]
    assert (full.n_experts, full.experts_held, full.top_k) == (72, 72, 10)
    assert 31e9 < Model(full).param_count() < 33e9      # 32B total
    share = get_config("granite-4.0-h-small-ep8")
    assert (share.n_layers, share.experts_held, share.n_experts) == (
        10, 9, 72)
    assert share.replace(n_layers=40, experts_held=72,
                         name=full.name) == full
    # About 2.4B parameters (4.8 GB in bf16) on the chip.
    assert 2.3e9 < Model(share).param_count() < 2.5e9


def test_reference_matches_published_code(monkeypatch):
    """The reference against ``transformers``' own Granite 4.0-H at a tiny
    size, with every expert held, on weights copied into the program's
    layout."""
    monkeypatch.setenv("USE_TF", "0")
    monkeypatch.setenv("USE_FLAX", "0")
    torch = pytest.importorskip("torch")
    hf = pytest.importorskip(
        "transformers.models.granitemoehybrid.modeling_granitemoehybrid")
    from transformers import GraniteMoeHybridConfig

    c = _config(num_local_experts=8, num_hidden_layers=4,
                layer_types=["mamba", "attention", "mamba", "mamba"] * 10)
    n_layers, E = c["num_hidden_layers"], 8
    hc = GraniteMoeHybridConfig(
        vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
        intermediate_size=c["intermediate_size"],
        num_hidden_layers=n_layers,
        num_attention_heads=c["num_attention_heads"],
        num_key_value_heads=c["num_key_value_heads"],
        rms_norm_eps=c["rms_norm_eps"], tie_word_embeddings=True,
        embedding_multiplier=c["embedding_multiplier"],
        logits_scaling=c["logits_scaling"],
        residual_multiplier=c["residual_multiplier"],
        attention_multiplier=c["attention_multiplier"],
        num_local_experts=E, num_experts_per_tok=c["num_experts_per_tok"],
        shared_intermediate_size=c["shared_intermediate_size"],
        position_embedding_type="nope",
        layer_types=c["layer_types"][:n_layers],
        mamba_n_heads=c["mamba_n_heads"], mamba_d_head=c["mamba_d_head"],
        mamba_d_state=c["mamba_d_state"], mamba_n_groups=1,
        mamba_d_conv=c["mamba_d_conv"], mamba_expand=c["mamba_expand"],
        mamba_chunk_size=8, mamba_conv_bias=True, mamba_proj_bias=False,
        attn_implementation="eager")
    torch.manual_seed(0)
    model = hf.GraniteMoeHybridForCausalLM(hc).eval()
    with torch.no_grad():
        for name, prm in model.named_parameters():
            if name.endswith("A_log"):
                prm.copy_(torch.log(torch.rand_like(prm) * 15 + 1))
            elif name.endswith(("dt_bias", "D", "conv1d.bias")):
                prm.copy_(torch.randn_like(prm) * 0.5)
            elif name.endswith("embed_tokens.weight"):
                prm.copy_(torch.randn_like(prm) * 2)  # logits of std ~1
            elif prm.ndim == 1:                       # norm weights
                prm.copy_(1 + 0.2 * torch.randn_like(prm))
            else:
                fan_in = prm.shape[-1]
                prm.copy_(torch.randn_like(prm) * 1.5 / fan_in ** 0.5)

    def a(t):
        return jnp.asarray(t.detach().numpy(), F32)

    f, fs = c["intermediate_size"], c["shared_intermediate_size"]
    layers = []
    for lyr in model.model.layers:
        moe, sh = lyr.block_sparse_moe, lyr.shared_mlp
        w_in = a(moe.input_linear.weight)              # (E, 2f, d)
        p = {"norm": a(lyr.input_layernorm.weight) - 1,
             "mlp_norm": a(lyr.post_attention_layernorm.weight) - 1,
             "router": a(moe.router.layer.weight).T,
             "we_gate": w_in[:, :f].transpose(0, 2, 1),
             "we_up": w_in[:, f:].transpose(0, 2, 1),
             "we_down": a(moe.output_linear.weight).transpose(0, 2, 1),
             "ws_gate": a(sh.input_linear.weight)[:fs].T,
             "ws_up": a(sh.input_linear.weight)[fs:].T,
             "ws_down": a(sh.output_linear.weight).T}
        if lyr.mamba is not None:
            m = lyr.mamba
            p.update({"in_proj": a(m.in_proj.weight).T,
                      "conv_w": a(m.conv1d.weight)[:, 0].T,
                      "conv_b": a(m.conv1d.bias), "A_log": a(m.A_log),
                      "D": a(m.D), "dt_bias": a(m.dt_bias),
                      "ssm_norm": a(m.norm.weight) - 1,
                      "out_proj": a(m.out_proj.weight).T})
        else:
            at = lyr.self_attn
            p.update({n: a(getattr(at, f"{n[1]}_proj").weight).T
                      for n in ("wq", "wk", "wv", "wo")})
        layers.append(p)
    w = {"embed": a(model.model.embed_tokens.weight),
         "final_norm": a(model.model.norm.weight) - 1,
         "blocks": {f"pos{i}": jax.tree.map(lambda x: x[None], p)
                    for i, p in enumerate(layers)}}

    tokens = np.random.default_rng(2).integers(0, c["vocab_size"], (2, 24))
    with torch.no_grad():
        want = model(torch.as_tensor(tokens)).logits.numpy()
    got = ref.reference_logits(c, w, jnp.asarray(tokens, jnp.int32),
                               jnp.arange(24))
    assert np.std(want) > 0.5
    assert _rel(got, want) < 1e-5
