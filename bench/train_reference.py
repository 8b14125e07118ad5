"""Plain reference of the training step that the `train_step` entry times:
a decoder-only LM (RMSNorm, rotary GQA attention, SwiGLU MLP, tied
embeddings) with next-token cross-entropy, its gradient and one AdamW
update, in straightforward `jax.numpy` at float32 under
`jax.default_matmul_precision("highest")`.

Written from the published description of the architecture (the Llama
block layout that SmolLM uses: pre-norm, rotary embeddings on the two
halves of each head, grouped KV heads shared by consecutive query heads,
SiLU-gated MLP) and from the configuration file's optimizer recipe.  It
imports nothing of `repro`.

The weights are the benchmark's, not the program's: `init_params` makes
them from the seed in one jitted call, in the program's parameter layout
(layers stacked on a leading axis) and in the stored type that the
configuration states, and the entry hands the same tree to the program
and to the reference.  Tokens come from `token_pool`, also from the seed.

The gradient is accumulated over blocks of `block_rows` sequences, each
layer rematerialised in the backward pass, so that the float32 step fits
next to nothing else on one chip.  `dot` is the one matmul of the model:
float32 at `highest` for the reference, `fp8_dot` for the control (every
matmul's operands, forward and backward, rounded to float8 e4m3 with one
scale per tensor, accumulated in float32).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
LEAVES = (("layers", "attn", "norm"), ("layers", "attn", "wq"),
          ("layers", "attn", "wk"), ("layers", "attn", "wv"),
          ("layers", "attn", "wo"), ("layers", "mlp", "norm"),
          ("layers", "mlp", "w_gate"), ("layers", "mlp", "w_up"),
          ("layers", "mlp", "w_down"), ("final_norm",), ("lm_head",))


# ------------------------------------------------------------ seeded inputs

def param_shapes(m: dict) -> dict:
    """{leaf path: shape} of the model `m` (the configuration's "model")."""
    L, d, f, V = m["n_layers"], m["d_model"], m["d_ff"], m["vocab"]
    qd, kvd = m["n_heads"] * m["head_dim"], m["n_kv_heads"] * m["head_dim"]
    return {("layers", "attn", "norm"): (L, d),
            ("layers", "attn", "wq"): (L, d, qd),
            ("layers", "attn", "wk"): (L, d, kvd),
            ("layers", "attn", "wv"): (L, d, kvd),
            ("layers", "attn", "wo"): (L, qd, d),
            ("layers", "mlp", "norm"): (L, d),
            ("layers", "mlp", "w_gate"): (L, d, f),
            ("layers", "mlp", "w_up"): (L, d, f),
            ("layers", "mlp", "w_down"): (L, f, d),
            ("final_norm",): (d,),
            ("lm_head",): (d, V)}


def nest(flat: dict) -> dict:
    out: dict = {}
    for path, v in flat.items():
        node = out
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = v
    return out


def leaf(tree: dict, path):
    for k in path:
        tree = tree[k]
    return tree


def init_params(key, m: dict):
    """Norm weights 1, matrices N(0, 1/fan_in) (fan_in the second-last
    axis), in the model's `param_dtype`; one key per leaf, in LEAVES
    order."""
    dtype = jnp.dtype(m["param_dtype"])
    keys = jax.random.split(key, len(LEAVES))
    flat = {}
    for k, (path, shape) in zip(keys, param_shapes(m).items()):
        if path[-1] in ("norm", "final_norm"):
            flat[path] = jnp.ones(shape, dtype)
        else:
            scale = 1.0 / math.sqrt(shape[-2])
            flat[path] = (jax.random.normal(k, shape, F32) * scale
                          ).astype(dtype)
    return nest(flat)


def token_pool(key, n: int, rows: int, seq: int, vocab: int):
    """`n` batches of `rows` token sequences of `seq + 1` ids, uniform
    over the vocabulary: inputs are the first `seq`, targets the last."""
    return jax.random.randint(key, (n, rows, seq + 1), 0, vocab, jnp.int32)


def split_batch(tokens):
    return tokens[..., :-1], tokens[..., 1:]


# ------------------------------------------------------------------- matmuls

def f32_dot(spec: str, a, b):
    return jnp.einsum(spec, a.astype(F32), b.astype(F32),
                      preferred_element_type=F32)


E4M3_MAX = 448.0


def _fp8(x):
    """x rounded to float8 e4m3 with one scale per tensor, back in f32."""
    amax = jnp.max(jnp.abs(x))
    s = jnp.where(amax > 0, amax / E4M3_MAX, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def fp8_dot(spec: str, a, b):
    return f32_dot(spec, _fp8(a), _fp8(b))


def _fp8_fwd(spec, a, b):
    qa, qb = _fp8(a), _fp8(b)
    return f32_dot(spec, qa, qb), (qa, qb)


def _fp8_bwd(spec, res, g):
    qa, qb = res
    _, vjp = jax.vjp(functools.partial(f32_dot, spec), qa, qb)
    return vjp(_fp8(g))


fp8_dot.defvjp(_fp8_fwd, _fp8_bwd)


# ---------------------------------------------------------------------- model

def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w


def rope(x, theta: float):
    """x (B, S, H, D): each head's two halves rotated by position."""
    S, D = x.shape[1], x.shape[-1]
    half = D // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    ang = jnp.arange(S, dtype=F32)[:, None] * freqs
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def layer(m: dict, dot, h, lp):
    B, S, _ = h.shape
    H, Hkv, D = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    a = lp["attn"]
    x = rms_norm(h, a["norm"], m["norm_eps"])
    q = dot("bsd,dq->bsq", x, a["wq"]).reshape(B, S, H, D)
    k = dot("bsd,dk->bsk", x, a["wk"]).reshape(B, S, Hkv, D)
    v = dot("bsd,dk->bsk", x, a["wv"]).reshape(B, S, Hkv, D)
    q, k = rope(q, m["rope_theta"]), rope(k, m["rope_theta"])
    q = q.reshape(B, S, Hkv, H // Hkv, D)       # query head = kv * G + g
    s = dot("bqhgd,bkhd->bhgqk", q, k) / math.sqrt(D)
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = dot("bhgqk,bkhd->bqhgd", p, v).reshape(B, S, H * D)
    h = h + dot("bsq,qd->bsd", o, a["wo"])
    f = lp["mlp"]
    x = rms_norm(h, f["norm"], m["norm_eps"])
    z = jax.nn.silu(dot("bsd,df->bsf", x, f["w_gate"])) * \
        dot("bsd,df->bsf", x, f["w_up"])
    return h + dot("bsf,fd->bsd", z, f["w_down"])


def nll_sum(params, inputs, targets, m: dict, dot):
    """Summed next-token negative log-likelihood of a block of rows."""
    h = jnp.take(params["lm_head"].T, inputs, axis=0)

    @jax.checkpoint
    def body(h, lp):
        return layer(m, dot, h, lp), None

    h, _ = jax.lax.scan(body, h, params["layers"])
    h = rms_norm(h, params["final_norm"], m["norm_eps"])
    logits = dot("bsd,dv->bsv", h, params["lm_head"])
    pick = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - pick)


@functools.partial(jax.jit, static_argnames=("m", "dot"))
def _block(params, inputs, targets, m, dot):
    return jax.value_and_grad(nll_sum)(params, inputs, targets, dict(m),
                                       dot)


def loss_and_grad(params, tokens, m: dict, dot=f32_dot,
                  block_rows: int = 2):
    """Mean next-token loss over every row of `tokens` (rows, seq + 1)
    and its gradient, both in float32 (the weights are taken to float32
    first), accumulated over blocks of rows."""
    inputs, targets = split_batch(tokens)
    mk = tuple(sorted(m.items()))
    params = jax.tree.map(lambda x: x.astype(F32), params)
    total, grad = 0.0, None
    with jax.default_matmul_precision("highest"):
        for i in range(0, inputs.shape[0], block_rows):
            s, g = _block(params, inputs[i:i + block_rows],
                          targets[i:i + block_rows], mk, dot)
            total = total + s
            grad = g if grad is None else jax.tree.map(jnp.add, grad, g)
    n = inputs.size
    return total / n, jax.tree.map(lambda g: g / n, grad)


# ------------------------------------------------------------------ optimizer

def learning_rate(step, opt: dict) -> float:
    """Linear warm-up to the peak, then a cosine to a tenth of it."""
    warm = min(step / max(opt["warmup_steps"], 1), 1.0)
    t = min(max((step - opt["warmup_steps"]) /
                max(opt["total_steps"] - opt["warmup_steps"], 1), 0.0), 1.0)
    return opt["learning_rate"] * warm * (0.1 + 0.45 * (1 + math.cos(
        math.pi * t)))


@jax.jit
def _adamw(p, g, m, v, t, lr, wd, b1, b2, eps):
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    upd = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps) + \
        wd * p.astype(F32)
    return (p.astype(F32) - lr * upd).astype(p.dtype), m, v


def adamw(params, grad, opt_state: dict, step_index, opt: dict):
    """One AdamW update; `opt_state` {"m", "v", "t"} (moments in float32,
    t the updates so far), the weights kept in their stored type."""
    t = opt_state["t"] + 1
    lr = learning_rate(step_index, opt)
    out = {path: _adamw(leaf(params, path), leaf(grad, path),
                        leaf(opt_state["m"], path),
                        leaf(opt_state["v"], path), float(t), lr,
                        opt["weight_decay"], opt["b1"], opt["b2"],
                        opt["eps"]) for path in LEAVES}
    return (nest({k: o[0] for k, o in out.items()}),
            {"m": nest({k: o[1] for k, o in out.items()}),
             "v": nest({k: o[2] for k, o in out.items()}), "t": t})


def zero_moments(params) -> dict:
    z = nest({path: jnp.zeros(leaf(params, path).shape, F32)
              for path in LEAVES})
    return {"m": z, "v": jax.tree.map(jnp.zeros_like, z), "t": 0}


def train(params, batches, step0: int, m: dict, opt: dict, dot=f32_dot,
          block_rows: int = 2, rows=None) -> dict:
    """The first `len(batches)` steps from `params` and zero moments:
    each step's loss, the first step's gradient and the weights after the
    last.  `rows` (a slice) keeps only those rows of every batch: a
    planted half-batch fault."""
    state = zero_moments(params)
    losses, first = [], None
    for i, tokens in enumerate(batches):
        if rows is not None:
            tokens = tokens[rows]
        loss, grad = loss_and_grad(params, tokens, m, dot, block_rows)
        losses.append(float(loss))
        if first is None:
            first = jax.tree.map(np.asarray, grad)
        params, state = adamw(params, grad, state, step0 + i, opt)
    return {"losses": losses, "grad": first, "params": params}
