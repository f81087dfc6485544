"""The dense decoder family: full-attention RoPE decoders with grouped-query
attention, optional q/k/v bias, SwiGLU, tied or untied unembedding and
MiniCPM's muP scalars (qwen2-1.5b, minicpm-2b).

Everything that is particular to the family: the program's model
configuration built from a configuration file (``program_config``), the
weights drawn for it (``draw_params``), its plain float32 reference
(``reference``, with the fp8 control) and its operation counts
(``linear_flops``, ``attention_dims``, ``prefill_flops``,
``decode_flops``). ``chipbench/spec.py`` documents the interface.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from chipbench import counts
from chipbench.reference import F32, HI, linear, rmsnorm, rope
from repro.configs.registry import get_config
from repro.models import api as mapi

# configuration-file key -> the program's ModelConfig field
FIELDS = {
    "num_hidden_layers": "n_layers", "hidden_size": "d_model",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "d_ff", "vocab_size": "vocab_size",
    "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings", "qkv_bias": "qkv_bias",
    "torch_dtype": "dtype",
}
# spreads of the random weights (standard deviations, or around 1 for
# norm scales); matrices are N(0, 1/fan_in) as the program's own init
NORM_SPREAD, BIAS_SPREAD = 0.1, 0.5


def head_dim(conf: dict) -> int:
    return conf.get("head_dim") or conf["hidden_size"] // conf["num_attention_heads"]


def mup_factors(conf: dict):
    """MiniCPM's muP scalars (arXiv:2404.06395): the embedding factor,
    the factor on each residual branch and the divisor of the logits;
    1, 1, 1 for a model that has none."""
    emb = float(conf.get("scale_emb") or 1.0)
    depth = conf.get("scale_depth")
    branch = depth / math.sqrt(conf["num_hidden_layers"]) if depth else 1.0
    base = conf.get("dim_model_base")
    logits = conf["hidden_size"] / base if base else 1.0
    return emb, branch, logits


# ---------------------------------------------------------------- program

def program_config(conf: dict):
    """The program's ModelConfig for configuration file ``conf``."""
    cfg = get_config(conf["arch"]).with_(**{f: conf[k] for k, f in FIELDS.items()})
    if cfg.family != "dense" or cfg.sliding_window or cfg.rope != "rope":
        raise ValueError(f"{conf['arch']}: the dense family's reference covers "
                         "dense full-attention RoPE models only")
    if cfg.resolved_head_dim != head_dim(conf):
        raise ValueError(f"head_dim {head_dim(conf)} is not what the program "
                         f"runs ({cfg.resolved_head_dim})")
    return cfg


def draw_params(conf: dict, cfg, key):
    """Every leaf of the tree that the program's ``model.init`` declares
    (its shapes only), drawn from ``key`` in the dtype served.

    Matrices are N(0, 1/fan_in); the embedding's rows, as the first layer
    sees them, have unit norm. The program has no muP scalars, so the
    published ones are folded into the weights it serves: the published
    (tied) embedding is drawn at 1/scale_emb of that spread, so that
    ``scale_emb`` times it is what the program looks up; each residual
    branch's output matrix (``wo``, ``wd``) is scaled by the branch
    factor; and the final norm's scale is divided by ``scale_emb`` and by
    the logit divisor. The function computed is the published one. (Drawn
    at the full spread, the scaled embedding would outweigh the branches,
    and through the tied unembedding every token would predict itself.)
    """
    model = mapi.get_model(cfg)
    shapes = jax.eval_shape(lambda k: model.init(k, cfg)[0],
                            jax.random.PRNGKey(0))
    leaves, tree = jax.tree_util.tree_flatten_with_path(shapes)
    emb_f, branch_f, logit_f = mup_factors(conf)
    if (emb_f, logit_f) != (1.0, 1.0) and not conf["tie_word_embeddings"]:
        raise ValueError("muP folding is written for tied embeddings")
    V, dt = conf["vocab_size"], jnp.dtype(conf["torch_dtype"])
    out = []
    for i, (path, sd) in enumerate(leaves):
        name = path[-1].key
        k = jax.random.fold_in(key, i)
        z = jax.random.normal(k, sd.shape, jnp.float32)
        if name == "scale":
            w = 1.0 + NORM_SPREAD * z
            if path[0].key == "ln_f":
                w = w / (emb_f * logit_f)
        elif name in ("bq", "bk", "bv"):
            w = BIAS_SPREAD * z
        elif name == "embed":       # rows past the vocabulary are padding
            w = z / math.sqrt(sd.shape[-1])
            w = jnp.where(jnp.arange(sd.shape[0])[:, None] < V, w, 0.0)
        elif name == "unembed":
            w = z / math.sqrt(sd.shape[0])
            w = jnp.where(jnp.arange(sd.shape[1])[None, :] < V, w, 0.0)
        else:                       # (layers, fan_in, fan_out)
            w = z / math.sqrt(sd.shape[-2])
            if name in ("wo", "wd"):
                w = w * branch_f
        out.append(w.astype(dt))
    return jax.tree_util.tree_unflatten(tree, out)


# -------------------------------------------------------------- reference

def _conf_key(conf: dict):
    keys = ("num_hidden_layers", "hidden_size", "num_attention_heads",
            "num_key_value_heads", "intermediate_size", "vocab_size",
            "rope_theta", "rms_norm_eps", "tie_word_embeddings",
            "scale_emb", "scale_depth", "dim_model_base", "head_dim")
    return tuple((k, conf.get(k)) for k in keys)


def reference(conf: dict, mode):
    """The jitted ``(hidden, logits)`` pair of the plain reference (or,
    with ``mode="fp8"``, its control) for configuration ``conf``.

    It follows the published description (RMSNorm, RoPE with
    rotate-half, grouped-query attention with optional q/k/v bias,
    SwiGLU, tied or untied unembedding, MiniCPM's muP scalars), reads the
    benchmark's weights by their names in the served tree, and imports
    nothing of the program. Layers run one at a time under ``lax.scan``,
    each cast to float32 as it is used, so that the whole model never
    sits on the device in float32."""
    return _reference(_conf_key(conf), mode)


@functools.lru_cache(maxsize=None)
def _reference(conf_key, mode):
    conf = dict(conf_key)
    H, KH = conf["num_attention_heads"], conf["num_key_value_heads"]
    hd, V = head_dim(conf), conf["vocab_size"]
    eps, theta = conf["rms_norm_eps"], conf["rope_theta"]
    emb_f, branch_f, logit_f = mup_factors(conf)

    def hidden(params, tokens):
        """tokens (T,) -> the final norm's output (T, d), float32."""
        T = tokens.shape[0]
        E = params["emb"]["embed"][:V].astype(F32) / emb_f     # published E
        h = emb_f * E[tokens]
        pos = jnp.arange(T)
        causal = pos[None, :] <= pos[:, None]

        def layer(h, lp):
            lp = jax.tree.map(lambda a: a.astype(F32), lp)
            at, ml = lp["attn"], lp["mlp"]
            x = rmsnorm(h, lp["ln1"]["scale"], eps)
            q, k, v = (linear(x, at[w], mode) + at.get(b, 0.0)
                       for w, b in (("wq", "bq"), ("wk", "bk"), ("wv", "bv")))
            q = rope(q.reshape(T, H, hd), pos, theta)
            k = rope(k.reshape(T, KH, hd), pos, theta)
            v = v.reshape(T, KH, hd)
            k, v = jnp.repeat(k, H // KH, 1), jnp.repeat(v, H // KH, 1)
            s = jnp.einsum("thd,shd->hts", q, k, precision=HI) * hd ** -0.5
            p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), -1)
            o = jnp.einsum("hts,shd->thd", p, v, precision=HI)
            h = h + branch_f * linear(o.reshape(T, H * hd),
                                      at["wo"] / branch_f, mode)
            x = rmsnorm(h, lp["ln2"]["scale"], eps)
            g = jax.nn.silu(linear(x, ml["wg"], mode)) * linear(x, ml["wu"], mode)
            h = h + branch_f * linear(g, ml["wd"] / branch_f, mode)
            return h, None

        h, _ = jax.lax.scan(layer, h, params["layers"])
        scale = params["ln_f"]["scale"].astype(F32) * emb_f * logit_f
        return rmsnorm(h, scale, eps)

    def logits(params, hn, sel):
        """Final norm's output (T, d) at rows ``sel`` (P,) -> float32
        logits (P, V)."""
        E = params["emb"]["embed"][:V].astype(F32) / emb_f
        W = E.T if "unembed" not in params["emb"] else \
            params["emb"]["unembed"][:, :V].astype(F32)
        return linear(hn[sel], W, mode) / logit_f

    return jax.jit(hidden), jax.jit(logits)


# ----------------------------------------------------------------- counts

def layer_matmul_params(conf: dict) -> int:
    """Weights of one layer's matrix products (q, k, v, o and SwiGLU)."""
    d, f = conf["hidden_size"], conf["intermediate_size"]
    H, KH, hd = conf["num_attention_heads"], conf["num_key_value_heads"], \
        head_dim(conf)
    return d * (H + 2 * KH) * hd + H * hd * d + 3 * d * f


def linear_flops(conf: dict) -> int:
    """Operations of the linear layers for one token through every layer."""
    return 2 * conf["num_hidden_layers"] * layer_matmul_params(conf)


def attention_dims(conf: dict):
    """(layers that attend, query heads, key/value heads, head size)."""
    return (conf["num_hidden_layers"], conf["num_attention_heads"],
            conf["num_key_value_heads"], head_dim(conf))


def prefill_flops(conf: dict, s: int) -> int:
    """A prompt of ``s`` real tokens: linear layers for every token,
    causal attention, and logits at the last position only."""
    return s * linear_flops(conf) \
        + counts.flash_attention_cost(attention_dims(conf), conf, s)[0] \
        + counts.unembed_flops(conf)


def decode_flops(conf: dict, live) -> int:
    """What one decode step adds to each slot's linear layers and logits:
    attention over each slot's ``live`` keys."""
    return counts.decode_attention_cost(attention_dims(conf), conf, live)[0]
