"""Plain reference for the ``sdar`` family: SDAR-30B-A3B-Chat's layer
trained by block diffusion, in float32.

Straight ``jax.numpy``, every matrix product at ``precision=HIGHEST``,
no kernels. Imports nothing of the program. The parameter tree is laid
out under the names the program's model reads (``tok_embed``,
``block<i>/attn/q/kernel`` ..., ``block<i>/mlp/w1/kernel``, ``head/
kernel``): that layout is the interface through which the benchmark
hands the same weights to both sides.

**The layer** (``config.json`` of JetLM/SDAR-30B-A3B-Chat, ``sdar_moe``):
``a = RMSNorm(h)``; ``q = a·Wq`` (32 heads of 128), ``k = a·Wk``, ``v =
a·Wv`` (4 heads of 128), no biases; ``q, k <- RoPE(RMSNorm_head(q or
k))``, θ = 1e6; ``o_i = Σ_j softmax_j(q_i·k_j/√128 + M_ij)·v_j``, eight
query heads to a key head; ``h' = h + o·Wo``; ``b = RMSNorm(h')``;
``p = softmax(b·Wr)`` over all 128 experts, ``S = top-8(p)``, ``w_e =
p_e / Σ_S p``; ``y = Σ_{e∈S, e held} w_e·W2_e(silu(W1_e b) ⊙ W3_e b)``;
``h'' = h' + y``. Final RMSNorm, untied head.

**The objective** (BD3-LM, arXiv:2503.09573, as SDAR, arXiv:2510.06303,
uses it): a row ``x0`` of L tokens in blocks of B; a level ``t ~
U(t_min, 1)`` a block, each of its tokens replaced by the mask id with
probability t, giving ``x_t``. The network reads ``[x_t ‖ x0]`` with
positions ``[0..L−1, 0..L−1]``; with β(i) = ⌊i/B⌋ the mask M allows
noised→noised iff same block, noised→clean iff β(j) < β(i), clean→clean
iff β(j) ≤ β(i), clean→noised never. The head reads the noised half;
loss = (1 / rows·L) Σ_masked CE_i / t_β(i).

**Departures and what is assumed** (the configuration file lists the
same under ``assumed``):

* the per-head q/k RMSNorm is the Qwen3-MoE modelling code's, from
  which ``sdar_moe`` derives; ``config.json`` has no key for it;
* block length 4, the linear schedule clipped at ``t_min`` = 1/8, no
  shift between a masked position and its target, and the mask id (the
  vocabulary slice's last) are not in ``config.json``;
* the share: this chip holds ``num_experts`` of the ``published``
  experts from ``first_expert`` on, and what the absent ones would add
  is left out of ``y``; the vocabulary is the slice's;
* the noising is redone here from the clean rows by the rule that
  ``data/noise.py`` of the program documents (a row's generator is
  PCG64 seeded with the CRC-32 of its bytes);
* the dense ``[2L, 2L]`` mask is applied a block of queries at a time,
  and layers, query blocks and experts are recomputed in the backward
  pass (``jax.checkpoint``), so that float32 at 8,192 positions fits:
  neither changes a number;
* weights are random from the seed (:func:`init_params`: the embedding
  at unit scale, the matrices at 0.02, GPT-2's scaling of the residual
  projections).
"""

from __future__ import annotations

import functools
import zlib
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 512  # queries that meet all 2L keys at once


# -- the configuration as the reference reads it -----------------------------

def sizes(cfg: dict) -> dict:
    return {
        "d": cfg["hidden_size"], "layers": cfg["layers"],
        "heads": cfg["num_attention_heads"], "kv": cfg["num_key_value_heads"],
        "hd": cfg["head_dim"], "f": cfg["moe_intermediate_size"],
        "held": cfg["num_experts"], "first": cfg.get("first_expert", 0),
        "experts": cfg["published"]["num_experts"],
        "top_k": cfg["num_experts_per_tok"], "vocab": cfg["vocab_size"],
        "eps": cfg["rms_norm_eps"], "theta": float(cfg["rope_theta"]),
        "block": cfg["assumed"]["block_length"],
        "t_min": cfg["assumed"]["t_min"],
        "mask_id": cfg["assumed"]["mask_token_id"],
    }


# -- shapes and weights ------------------------------------------------------

def param_shapes(cfg: dict) -> Dict[str, tuple]:
    """Flat ``path -> shape`` of the family's parameters."""
    s = sizes(cfg)
    d, f, held = s["d"], s["f"], s["held"]
    out = {"tok_embed": (s["vocab"], d)}
    for i in range(s["layers"]):
        b = f"block{i}/"
        out.update({
            b + "ln1/scale": (d,),
            b + "attn/q/kernel": (d, s["heads"] * s["hd"]),
            b + "attn/k/kernel": (d, s["kv"] * s["hd"]),
            b + "attn/v/kernel": (d, s["kv"] * s["hd"]),
            b + "attn/q_norm/scale": (s["hd"],),
            b + "attn/k_norm/scale": (s["hd"],),
            b + "attn/o/kernel": (s["heads"] * s["hd"], d),
            b + "ln2/scale": (d,),
            b + "mlp/router/kernel": (d, s["experts"]),
            b + "mlp/w1/kernel": (held, d, f),
            b + "mlp/w3/kernel": (held, d, f),
            b + "mlp/w2/kernel": (held, f, d),
        })
    out.update({"ln_final/scale": (d,), "head/kernel": (d, s["vocab"])})
    return out


def param_count(cfg: dict) -> int:
    return int(sum(np.prod(s) for s in param_shapes(cfg).values()))


def nest(flat: Dict[str, object]) -> dict:
    out: dict = {}
    for path, leaf in flat.items():
        node = out
        parts = path.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = leaf
    return out


def flatten(tree, prefix: str = "") -> Dict[str, object]:
    out = {}
    for k, v in tree.items():
        if hasattr(v, "items"):
            out.update(flatten(v, prefix + k + "/"))
        else:
            out[prefix + k] = v
    return out


def seed_key(seed: int):
    """A PRNG key from any whole number (the driver's seeds pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), (seed >> 31) & 0x7FFFFFFF
    )


def init_params(cfg: dict, seed: int) -> dict:
    """Every weight from the seed, float32, in one jitted call: matrices
    N(0, 0.02), those that write into the residual stream (attention's
    ``o``, the experts' ``w2``) scaled by 1/sqrt(2 x published layers) as
    GPT-2 initialises them; norm scales 1 + N(0, 0.1) rather than the
    customary 1, so that a scale one side dropped would show; the
    embedding N(0, 1). The last two keep a position's hidden state its
    token's through the random layers, as a trained model's is: with
    everything at 0.02 the uniform average of a random attention swamps
    the residual stream from the second layer on, every position routes
    alike, and a layer's load on the held experts is nothing or every
    token (found on the chip, PR 27)."""
    residual = (2.0 * cfg["published"]["layers"]) ** -0.5
    shapes = param_shapes(cfg)

    @jax.jit
    def make(key):
        flat = {}
        for i, (path, shape) in enumerate(shapes.items()):
            z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
            if path.endswith("/scale"):
                flat[path] = 1.0 + 0.1 * z
            elif path == "tok_embed":
                flat[path] = z
            elif path.endswith(("attn/o/kernel", "mlp/w2/kernel")):
                flat[path] = 0.02 * residual * z
            else:
                flat[path] = 0.02 * z
        return nest(flat)

    return make(seed_key(seed))


# -- the noising -------------------------------------------------------------

def noise_rows(tokens, cfg: dict):
    """Clean rows ``[R, L]`` -> ``(inputs [R, 2L], targets [R, L] with
    −1 where not masked, weights [R, L] = 1/t where masked)`` by the
    rule at the top."""
    s = sizes(cfg)
    block, t_min = s["block"], s["t_min"]
    tokens = np.asarray(tokens, np.int32)
    rows, length = tokens.shape
    inputs = np.empty((rows, 2 * length), np.int32)
    targets = np.full((rows, length), -1, np.int32)
    weights = np.zeros((rows, length), np.float32)
    for r in range(rows):
        rng = np.random.Generator(
            np.random.PCG64(zlib.crc32(tokens[r].astype("<i4").tobytes()))
        )
        t = np.repeat(t_min + (1.0 - t_min) * rng.random(length // block), block)
        hit = rng.random(length) < t
        inputs[r, :length] = np.where(hit, s["mask_id"], tokens[r])
        inputs[r, length:] = tokens[r]
        targets[r, hit] = tokens[r, hit]
        weights[r, hit] = (1.0 / t[hit]).astype(np.float32)
    return inputs, targets, weights


# -- lower precisions (the controls) -----------------------------------------

def _ste(x, q):
    return x + jax.lax.stop_gradient(q - x)


def cast_int8(x, axis):
    """Symmetric int8 with one scale along ``axis`` (the contraction
    axis)."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    return _ste(x, jnp.clip(jnp.round(x / scale), -127, 127) * scale)


CASTS: Dict[str, Optional[Callable]] = {"float32": None, "int8": cast_int8}


def _matmul(x, w, cast):
    """``x[..., k] @ w[k, n]`` at HIGHEST. Under a control the operands
    of the product and of both products of its backward pass are first
    rounded to the control's precision (float32 accumulation)."""
    if cast is None:
        return jnp.matmul(x, w, precision=HIGHEST)
    value = jax.lax.stop_gradient

    @jax.custom_vjp
    def f(x, w):
        return jnp.matmul(value(cast(x, -1)), value(cast(w, 0)), precision=HIGHEST)

    def fwd(x, w):
        xq, wq = value(cast(x, -1)), value(cast(w, 0))
        return jnp.matmul(xq, wq, precision=HIGHEST), (xq, wq)

    def bwd(res, g):
        xq, wq = res
        x2, g2 = xq.reshape(-1, xq.shape[-1]), g.reshape(-1, g.shape[-1])
        dx = jnp.matmul(value(cast(g, -1)), wq.T, precision=HIGHEST)
        dw = jnp.matmul(x2.T, value(cast(g2, 0)), precision=HIGHEST)
        return dx, dw

    f.defvjp(fwd, bwd)
    return f(x, w)


# -- forward -----------------------------------------------------------------

def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * scale


def _rope(x, positions, theta):
    """``x [R, T, H, d]``: the halves of a head rotated against each
    other by ``positions·θ^(−2i/d)``."""
    half = x.shape[-1] // 2
    freq = theta ** (-np.arange(half, dtype=np.float32) / half)
    angle = positions[:, None].astype(jnp.float32) * freq[None, :]
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def allowed(q_index, k_index, length: int, block: int):
    """M over ``[noised ‖ clean]``: True where query ``q_index`` may see
    key ``k_index`` (both in ``0 .. 2L−1``)."""
    q_noised, k_noised = q_index < length, k_index < length
    bq, bk = (q_index % length) // block, (k_index % length) // block
    return jnp.where(
        q_noised,
        jnp.where(k_noised, bq == bk, bk < bq),
        jnp.where(k_noised, False, bk <= bq),
    )


def _attention(q, k, v, length: int, block: int):
    """``q [R, 2L, H, d]`` against ``k, v [R, 2L, KV, d]`` under M, a
    block of queries against all the keys at a time."""
    r, t2, h, d = q.shape
    rep = h // k.shape[2]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    qb = min(QUERY_BLOCK, t2)
    k_index = jnp.arange(t2)

    @jax.checkpoint
    def one(args):
        start, q_blk = args  # q_blk [R, qb, H, d]
        scores = jnp.einsum(
            "rqhd,rkhd->rhqk", q_blk, k, precision=HIGHEST
        ) / np.sqrt(d).astype(np.float32)
        mask = allowed((start + jnp.arange(qb))[:, None], k_index[None, :], length, block)
        probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        return jnp.einsum("rhqk,rkhd->rqhd", probs, v, precision=HIGHEST)

    blocks = q.reshape(r, t2 // qb, qb, h, d).transpose(1, 0, 2, 3, 4)
    out = jax.lax.map(one, (jnp.arange(0, t2, qb), blocks))
    return out.transpose(1, 0, 2, 3, 4).reshape(r, t2, h * d)


def route(b, router, top_k: int):
    """Softmax over all the experts, the ``top_k`` largest renormalised:
    expert ids and gates ``[T, top_k]``."""
    probs = jax.nn.softmax(jnp.matmul(b, router, precision=HIGHEST), axis=-1)
    gates, experts = jax.lax.top_k(probs, top_k)
    return experts, gates / jnp.sum(gates, -1, keepdims=True)


def _experts(b, p, s, cast):
    """The held experts' part of the mixture for ``b [T, D]``, one
    expert after another over all the tokens."""
    experts, gates = route(b, p["router"]["kernel"], s["top_k"])

    @jax.checkpoint
    def one(y, args):
        e, w1, w3, w2 = args
        gate = jnp.sum(jnp.where(experts == e, gates, 0.0), axis=-1)
        up = jax.nn.silu(_matmul(b, w1, cast)) * _matmul(b, w3, cast)
        return y + gate[:, None] * _matmul(up, w2, cast), None

    ids = s["first"] + jnp.arange(s["held"])
    y, _ = jax.lax.scan(
        one, jnp.zeros_like(b),
        (ids, p["w1"]["kernel"], p["w3"]["kernel"], p["w2"]["kernel"]),
    )
    return y, experts


def _layer(x, p, positions, s, cast):
    r, t2, d = x.shape
    a = _rms_norm(x, p["ln1"]["scale"], s["eps"])
    at = p["attn"]
    q = _matmul(a, at["q"]["kernel"], cast).reshape(r, t2, s["heads"], s["hd"])
    k = _matmul(a, at["k"]["kernel"], cast).reshape(r, t2, s["kv"], s["hd"])
    v = _matmul(a, at["v"]["kernel"], cast).reshape(r, t2, s["kv"], s["hd"])
    q = _rope(_rms_norm(q, at["q_norm"]["scale"], s["eps"]), positions, s["theta"])
    k = _rope(_rms_norm(k, at["k_norm"]["scale"], s["eps"]), positions, s["theta"])
    if cast is not None:
        q, k, v = cast(q, -1), cast(k, -1), cast(v, -1)
    o = _attention(q, k, v, t2 // 2, s["block"])
    x = x + _matmul(o, at["o"]["kernel"], cast)
    b = _rms_norm(x, p["ln2"]["scale"], s["eps"])
    y, experts = _experts(b.reshape(r * t2, d), p["mlp"], s, cast)
    return x + y.reshape(r, t2, d), experts


def forward(params: dict, inputs, cfg: dict, cast=None):
    """``[R, 2L]`` tokens, noised then clean -> float32 logits ``[R, L,
    vocab]`` of the noised half, and the experts each position chose,
    ``[layers, R·2L, top_k]``."""
    s = sizes(cfg)
    length = inputs.shape[1] // 2
    positions = jnp.concatenate([jnp.arange(length)] * 2)
    x = params["tok_embed"][inputs]
    chosen = []
    layer = jax.checkpoint(functools.partial(_layer, s=s, cast=cast))
    for i in range(s["layers"]):
        x, experts = layer(x, params[f"block{i}"], positions)
        chosen.append(experts)
    x = _rms_norm(x[:, :length], params["ln_final"]["scale"], s["eps"])
    return _matmul(x, params["head"]["kernel"], cast), jnp.stack(chosen)


def diffusion_loss(params, inputs, targets, weights, cfg, cast=None):
    """``Σ_masked CE/t`` over ``rows·L``."""
    logits, _ = forward(params, inputs, cfg, cast)
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(
        logits, jnp.maximum(targets, 0)[..., None], axis=-1
    )[..., 0]
    return jnp.sum(jnp.where(targets >= 0, (logz - picked) * weights, 0.0)) / targets.size


def held_pairs(experts, cfg: dict):
    """Of the choices ``[layers, T, top_k]``, how many fall on held
    experts, a layer (their mean)."""
    s = sizes(cfg)
    on = (experts >= s["first"]) & (experts < s["first"] + s["held"])
    return jnp.mean(jnp.sum(on, axis=(1, 2)).astype(jnp.float32))


# -- training ----------------------------------------------------------------

def adamw_step(params, grads, mu, nu, count, opt: dict):
    """AdamW as optax.adamw computes it: bias-corrected moments, ``eps``
    outside the root, decoupled decay on the matrices (leaves named
    ``kernel``) only, a constant learning rate."""
    b1, b2, eps = opt["adam_beta1"], opt["adam_beta2"], opt["adam_eps"]
    lr, wd = opt["learning_rate"], opt["decoupled_weight_decay"]
    count = count + 1
    flat_p, flat_g = flatten(params), flatten(grads)
    flat_mu, flat_nu = flatten(mu), flatten(nu)
    new_p, new_mu, new_nu = {}, {}, {}
    for path, p in flat_p.items():
        g = flat_g[path]
        m = b1 * flat_mu[path] + (1 - b1) * g
        n = b2 * flat_nu[path] + (1 - b2) * g * g
        upd = (m / (1 - b1 ** count)) / (jnp.sqrt(n / (1 - b2 ** count)) + eps)
        if path.endswith("/kernel"):
            upd = upd + wd * p
        new_p[path], new_mu[path], new_nu[path] = p - lr * upd, m, n
    return nest(new_p), nest(new_mu), nest(new_nu), count


def compared_as(path: str) -> str:
    """The leaf of the comparison that the parameter at ``path`` belongs
    to: the experts' kernels of all the layers are one leaf, the routers'
    kernels another, every other parameter a leaf of its own.

    Routing is discontinuous, and under this objective its flips are not
    independent. Only masked positions carry loss, and they all hold the
    mask token's embedding, so in a layer they choose (all but) the same
    eight experts. Where none of those is held (one layer in three:
    (112/128)^8) the held experts' gradient is a tenth of another
    layer's and comes from the few hundred positions whose choice was
    marginal, the very ones that float32 and bfloat16 decide otherwise;
    where the mask token's eighth and ninth choices nearly tie and one of
    them is held, a tenth of the masked positions change sides together.
    Such a layer's experts read 0.06-0.18 off in sound runs (12 seeds on
    the chip, PR 27, call 42), and a single router's kernel up to 0.52,
    while over all the layers the experts read at most 0.009 and the
    routers 0.016."""
    if "/mlp/router/" in path:
        return "mlp/routers"
    if "/mlp/w" in path:
        return "mlp/experts"
    return path


def _pooled(sums: Dict[str, object]) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for path, v in sums.items():
        out[compared_as(path)] = out.get(compared_as(path), 0.0) + float(v)
    return {k: float(np.sqrt(v)) for k, v in out.items()}


@jax.jit
def _sums_of_squares(tree):
    return {k: jnp.sum(jnp.square(v.astype(jnp.float32)))
            for k, v in flatten(tree).items()}


def leaf_norms(tree) -> Dict[str, float]:
    """L2 norms over the leaves of the comparison (:func:`compared_as`)."""
    return _pooled(_sums_of_squares(tree))


def train_reference(params, batches, cfg: dict, opt: dict, *,
                    cast=None, rows_per_block: int = 1,
                    keep_rows: Optional[slice] = None,
                    freeze: bool = False) -> dict:
    """Follow ``len(batches)`` AdamW steps from ``params``.

    ``batches``: the clean ``(tokens [R, L], _)`` rows the program's
    staging was handed; the noising is redone here. The batch is taken
    ``rows_per_block`` rows at a time and the block gradients averaged.
    Returns each step's loss, the per-leaf norm of the first gradient
    and of the parameters' change after the last step. ``keep_rows`` and
    ``freeze`` plant the faults of the benchmark's tests."""
    grad_fn = jax.jit(jax.value_and_grad(
        lambda p, x, y, w: diffusion_loss(p, x, y, w, cfg, cast)
    ))
    # float32 state of 0.65B parameters is 2.4 GiB a copy: sums and
    # updates are made in place, the moments wait on the host while a
    # gradient is computed, and so do the first parameters
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b), donate_argnums=(0,))
    scale = jax.jit(lambda a, s: jax.tree.map(lambda v: v * s, a), donate_argnums=(0,))
    step = jax.jit(
        functools.partial(adamw_step, opt=opt), static_argnums=(4,),
        donate_argnums=(0, 2, 3),
    )
    p0 = jax.device_get(params)
    mu = nu = None
    losses, g1 = [], None
    for i, batch in enumerate(batches):
        x, y, w = noise_rows(batch[0], cfg)
        if keep_rows is not None:
            x, y, w = x[keep_rows], y[keep_rows], w[keep_rows]
        n = x.shape[0]
        if n % rows_per_block:
            raise ValueError(f"{n} rows do not divide into blocks of {rows_per_block}")
        total, loss = None, 0.0
        for s in range(0, n, rows_per_block):
            rows = slice(s, s + rows_per_block)
            l, g = grad_fn(params, x[rows], y[rows], w[rows])
            total = g if total is None else add(total, g)
            loss += float(l)
        k = n // rows_per_block
        grads = scale(total, 1.0 / k)
        losses.append(loss / k)
        if i == 0:
            g1 = leaf_norms(grads)
        if not freeze:
            if mu is None:
                mu = jax.tree.map(np.zeros_like, p0)
                nu = jax.tree.map(np.zeros_like, p0)
            params, mu, nu, _ = step(
                params, grads, jax.device_put(mu), jax.device_put(nu), i
            )
            del grads
            mu, nu = jax.device_get(mu), jax.device_get(nu)
    first = flatten(p0)
    delta = _pooled({
        k: jnp.sum(jnp.square(v - jnp.asarray(first[k])))
        for k, v in flatten(params).items()
    })
    return {"losses": losses, "grad_norms": g1, "delta_norms": delta}


# -- operations and bytes, from shapes ---------------------------------------

def live_pairs(length: int, block: int) -> float:
    """(query, key) pairs M allows in a row of ``length`` clean tokens:
    clean→clean ``L(L+B)/2``, noised→clean ``L(L−B)/2``, noised→own
    block ``L·B``: ``L² + L·B``."""
    return float(length * length + length * block)


def _per_position(cfg: dict) -> float:
    """Multiply-adds a position passes through in a layer, outside the
    attention core: projections, router, and the expected share of its
    ``top_k`` experts that is held."""
    s = sizes(cfg)
    proj = s["d"] * s["hd"] * (2 * s["heads"] + 2 * s["kv"])
    expected = s["top_k"] * s["held"] / s["experts"] * 3 * s["d"] * s["f"]
    return proj + s["d"] * s["experts"] + expected


def forward_flops(cfg: dict, length: int) -> float:
    """One row's forward pass: ``2L`` positions through the layers, the
    live pairs once a layer (``QKᵀ`` and ``PV``: 4·d a pair a head), the
    head over the ``L`` noised positions. No recomputation, no padding,
    no tile above the mask."""
    s = sizes(cfg)
    core = 4.0 * s["hd"] * s["heads"] * live_pairs(length, s["block"])
    return (
        s["layers"] * (2.0 * _per_position(cfg) * 2 * length + core)
        + 2.0 * s["d"] * s["vocab"] * length
    )


def train_flops_per_sequence(cfg: dict, length: int) -> float:
    """Forward plus backward (twice the forward) for one row of
    ``length`` clean tokens."""
    return 3.0 * forward_flops(cfg, length)


def attn_core_cost(cfg: dict, length: int, rows: float) -> Dict[str, float]:
    """The least a step's attention cores need, all layers, forward and
    backward: 4·d a live pair a head forward and twice that backward;
    bytes with q, k, v and the output once forward, those and the
    output's cotangent read and the three gradients written backward, in
    the compute type."""
    s = sizes(cfg)
    wide, narrow = s["heads"] * s["hd"], s["kv"] * s["hd"]
    per_position = (2 * wide + 2 * narrow) + (3 * wide + 2 * narrow) + (wide + 2 * narrow)
    return {
        "flops": 3.0 * 4.0 * s["hd"] * s["heads"] * live_pairs(length, s["block"])
                 * rows * s["layers"],
        "bytes": 2.0 * per_position * 2 * length * rows * s["layers"],
    }


def expert_cost(cfg: dict, pairs_a_layer: float) -> Dict[str, float]:
    """The least a step's expert products need for ``pairs_a_layer``
    (token, held expert) pairs in each layer: three products a pair,
    forward and backward; bytes with the held experts' weights read
    once and each pair's row in and out, in the compute type."""
    s = sizes(cfg)
    weights = 3 * s["held"] * s["d"] * s["f"]
    return {
        "flops": 3.0 * 2.0 * 3 * s["d"] * s["f"] * pairs_a_layer * s["layers"],
        "bytes": 2.0 * (weights + 2 * s["d"] * pairs_a_layer) * s["layers"],
    }
