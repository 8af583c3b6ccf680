"""The llama family: a dense decoder with grouped-query attention, rotary
embedding (rotate-half), RMSNorm, a SiLU-gated MLP and an LM head tied to
the embedding; the program's ``family == "dense"`` entries without MLA,
qkv bias or local/global attention (deepseek-67b, mistral-large-123b).
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from bench import weights
from bench.reference import HIGHEST, Q_CHUNK, lin, rms

# program ArchConfig field <- configuration-file key
_FIELDS = {"n_layers": "num_hidden_layers", "d_model": "hidden_size",
           "n_heads": "num_attention_heads", "n_kv": "num_key_value_heads",
           "d_ff": "intermediate_size", "vocab": "vocab_size",
           "head_dim": "head_dim", "rope_theta": "rope_theta",
           "norm_eps": "rms_norm_eps"}
# fields the configuration file may set apart from the program's own entry
_CUT = ("n_layers", "norm_eps")

# role ids of ``weights.tensor``: fixed, so a seed gives the same bits for
# as long as the benchmark stands (0 is the embedding's)
ROLES = {"wq": 1, "wk": 2, "wv": 3, "wo": 4, "wg": 5, "wu": 6, "wd": 7}


def arch_fields(model: dict, base, check: bool) -> dict:
    """``ArchConfig`` fields for the configuration file.  With ``check``,
    every width must equal the program's own entry ``base``: a drift there
    would change the model under the cell."""
    kw = {f: type(getattr(base, f))(model[k]) if getattr(base, f) is not None
          else model[k] for f, k in _FIELDS.items()}
    if check:
        off = {f: (getattr(base, f), v) for f, v in kw.items()
               if f not in _CUT and getattr(base, f) != v}
        if off:
            raise ValueError(f"{model['name']}: configuration differs from "
                             f"the program's {model['program_arch']}: {off}")
    if base.family != "dense" or base.mla or base.act != "silu" \
            or base.qkv_bias or base.local_global_period:
        raise ValueError(f"{model['program_arch']} is not a llama-"
                         f"architecture dense model")
    return kw


def shapes(model: dict) -> Dict[str, Tuple[int, int]]:
    """``[out, in]`` of each linear of one layer, from the configuration."""
    d = model["hidden_size"]
    hd = model["head_dim"]
    h, kv = model["num_attention_heads"], model["num_key_value_heads"]
    f = model["intermediate_size"]
    return {"wq": (h * hd, d), "wk": (kv * hd, d), "wv": (kv * hd, d),
            "wo": (d, h * hd), "wg": (f, d), "wu": (f, d), "wd": (d, f)}


def layer(key, model: dict, index) -> Dict[str, jax.Array]:
    """The dense bf16 linears of layer ``index`` (may be traced)."""
    return {name: weights.tensor(key, ROLES[name], index, shp,
                                 shp[1] ** -0.5)
            for name, shp in shapes(model).items()}


def params(key, model: dict, cfg, pack):
    """The program's compressed tree.  Layers are made one at a time
    (``lax.map``), so no more than one dense layer is ever resident."""
    d = cfg.d_model

    def norm():
        return {"scale": jnp.ones((d,), jnp.float32)}

    def one(key, index):
        packed = {n: pack(w) for n, w in layer(key, model, index).items()}
        return {"ln1": norm(),
                "attn": {n: packed[n] for n in ("wq", "wk", "wv", "wo")},
                "ln2": norm(),
                "mlp": {n: packed[n] for n in ("wg", "wu", "wd")}}

    layers = jax.lax.map(lambda i: one(key, i),
                         jnp.arange(cfg.n_layers, dtype=jnp.int32))
    return {"embed": {"emb": weights.embedding(key, model)},
            "layers": layers, "lnf": norm()}


# -- plain reference (float32, ``bench.reference``) --------------------------

def _rope(x, theta):
    t, _, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def block(md: dict, w, h):
    """One layer over a padded sequence ``h`` ``[t, d]``: grouped-query
    causal attention in chunks of ``Q_CHUNK`` queries, then the MLP."""
    t = h.shape[0]
    nh, kv, hd = (md["num_attention_heads"], md["num_key_value_heads"],
                  md["head_dim"])
    eps, theta = md["rms_norm_eps"], md["rope_theta"]
    x = rms(h, eps)
    q = _rope(lin(x, w["wq"]).reshape(t, nh, hd), theta)
    k = _rope(lin(x, w["wk"]).reshape(t, kv, hd), theta)
    v = lin(x, w["wv"]).reshape(t, kv, hd)
    g = nh // kv
    qg = q.reshape(t // Q_CHUNK, Q_CHUNK, kv, g, hd)
    pos_k = jnp.arange(t)

    def chunk(args):
        qc, start = args
        s = jnp.einsum("qkgd,tkd->kgqt", qc, k,
                       precision=HIGHEST) * hd ** -0.5
        pos_q = start + jnp.arange(Q_CHUNK)
        s = jnp.where(pos_k[None, :] <= pos_q[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("kgqt,tkd->qkgd", p, v, precision=HIGHEST)

    o = jax.lax.map(chunk, (qg, jnp.arange(0, t, Q_CHUNK)))
    h = h + lin(o.reshape(t, nh * hd), w["wo"])
    x = rms(h, eps)
    a = lin(x, w["wg"])
    return h + lin(jax.nn.silu(a) * lin(x, w["wu"]), w["wd"])


def head(md: dict, emb, h):
    """Logits of the rows ``h``: the final RMSNorm, then the tied head."""
    return lin(rms(h, md["rms_norm_eps"]), emb)


# -- required work of one decode tick (``bench/metrics``) --------------------

def nm_spmv_linears(model: dict):
    """The n:m linears one decode tick runs per live row, as groups of
    (layers, ``[out, in]`` of each linear of one such layer)."""
    return [(model["num_hidden_layers"], list(shapes(model).values()))]


def decode_flops(model: dict, kv_lens) -> float:
    """FLOPs of one decode tick.  Per live row: 2 per kept (non-zero)
    linear weight, 2 per LM head weight (vocab x hidden), and
    4 * context * heads * head_dim per layer for attention.  Idle slots and
    padding count nothing."""
    n, m = model["sparsity"]["n"], model["sparsity"]["m"]
    kept = sum(o * k for o, k in shapes(model).values()) * n // m
    layers = model["num_hidden_layers"]
    per_row = 2.0 * (kept * layers
                     + model["vocab_size"] * model["hidden_size"])
    attn = 4.0 * model["num_attention_heads"] * model["head_dim"] * layers
    return per_row * len(kv_lens) + attn * float(sum(int(x) for x in kv_lens))


def paged_attn_kernel(model: dict) -> str:
    """The fused paged GQA decode attention as the trace prints it: its
    ``pallas_call`` has no ``name=``, so ``%closed_call.<n>`` with its
    float32 result ``[slots, heads, kv_heads * head_dim]``."""
    lanes = model["num_key_value_heads"] * model["head_dim"]
    return (rf"^%closed_call\.\d+ f32\[\d+,"
            rf"{model['num_attention_heads']},{lanes}\]$")


def paged_attn_work(model: dict, length: int):
    """(FLOPs, bytes) one layer's attention requires for one row of
    context ``length``: its K and V in bf16, scores and context."""
    h, kv, hd = (model["num_attention_heads"], model["num_key_value_heads"],
                 model["head_dim"])
    return 4.0 * length * h * hd, 2 * length * kv * hd * 2
