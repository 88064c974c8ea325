"""Plain reference for the ``smallthinker`` family: SmallThinker-21BA3B-
Instruct's layers trained on next-token prediction, in float32.

Straight ``jax.numpy``, every matrix product at ``precision=HIGHEST``,
no kernels. Imports nothing of the program; the tree helpers, the
lower-precision product of the controls, AdamW and the pooled leaf norms
are the ``sdar`` reference's, which are the family-blind parts of it.
The parameter tree lies under the names the program's model reads
(``tok_embed``, ``block<i>/attn/q/kernel`` ..., ``block<i>/mlp/router/
kernel``, ``head/kernel``): that layout is the interface through which
the benchmark hands the same weights to both sides.

**The layer** (``config.json`` of PowerInfer/SmallThinker-21BA3B-
Instruct and the family's modelling code; the report is
arXiv:2507.20984), ``x [T, 2560]``, layer ``l``:

* ``u = RMSNorm(x)``; ``r = u·W_router`` ``[T, 64]``: the router reads
  the same normed input as attention;
* ``q = u·Wq`` (28 heads of 128), ``k = u·Wk``, ``v = u·Wv`` (4 heads of
  128), no bias, no q/k norm; where ``rope_layout[l]`` is 1, ``q, k <-
  RoPE(q or k)``, θ = 1.5e6, halves rotated against each other; where it
  is 0, no positions at all;
* ``o_i = Σ_j softmax_j(q_i·k_j/√128)·v_j`` over the keys ``j <= i``
  and, where ``sliding_window_layout[l]`` is 1, ``j > i − 4096``; seven
  query heads to a key head; ``x' = x + o·Wo``;
* ``h = RMSNorm(x')``; ``S`` = the six largest of ``r``, gates =
  softmax over those six logits; ``y = Σ_{e∈S, e held} g_e·W2_e(relu(
  W1_e h) ⊙ W3_e h)``; ``x'' = x' + y``. No shared expert.

Final RMSNorm, untied head; the loss is the mean next-token
cross-entropy over every position.

**Departures and what is assumed** (the configuration file lists the
same under ``assumed``): ReLU as the gate (the catalog's row lost
``hidden_act``; its summary and the report say ReGLU); the router's
input (``assumed.router_input``: ``ln1``, not checked against the
modelling code, which is not in this repository); a query sees itself
and the 4,095 keys before it; the share: this chip holds
``moe_num_primary_experts`` of the ``published`` experts from
``first_expert`` on, what the absent ones would add is left out of
``y``, and the vocabulary is the slice's; weights random from the seed
(:func:`init_params`). Queries meet the keys a block at a time, and
layers, query blocks and experts are recomputed in the backward pass
(``jax.checkpoint``), so that float32 at 16,384 positions fits beside
nothing else: neither changes a number.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.references.sdar import (  # noqa: F401  (CASTS, leaf_norms: the runner's)
    CASTS,
    HIGHEST,
    _matmul,
    _pooled,
    _rms_norm,
    _rope,
    adamw_step,
    flatten,
    leaf_norms,
    nest,
    seed_key,
)

QUERY_BLOCK = 512  # queries that meet all the keys at once


# -- the configuration as the reference reads it -----------------------------

def sizes(cfg: dict) -> dict:
    layers = cfg["layers"]
    return {
        "d": cfg["hidden_size"], "layers": layers,
        "heads": cfg["num_attention_heads"], "kv": cfg["num_key_value_heads"],
        "hd": cfg["head_dim"], "f": cfg["moe_ffn_hidden_size"],
        "held": cfg["moe_num_primary_experts"],
        "first": cfg.get("first_expert", 0),
        "experts": cfg["published"]["moe_num_primary_experts"],
        "top_k": cfg["moe_num_active_primary_experts"],
        "vocab": cfg["vocab_size"], "eps": cfg["rms_norm_eps"],
        "theta": float(cfg["rope_theta"]),
        "window": cfg["sliding_window_size"],
        "windowed": tuple(bool(x) for x in cfg["sliding_window_layout"][:layers]),
        "rope": tuple(bool(x) for x in cfg["rope_layout"][:layers]),
        "router_input": cfg["assumed"]["router_input"],
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


def init_params(cfg: dict, seed: int) -> dict:
    """Every weight from the seed, float32, in one jitted call, as the
    ``sdar`` family draws them and for its reasons: matrices N(0, 0.02),
    those that write into the residual stream (attention's ``o``, the
    experts' ``w2``) scaled by 1/sqrt(2 x published layers); norm scales
    1 + N(0, 0.1), so that a scale one side dropped would show; the
    embedding N(0, 1), which keeps a position's hidden state its token's
    through random layers, so that positions route apart."""
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


def noise_rows(tokens, cfg: dict):  # noqa: ARG001
    """What the model reads of a batch's rows: a causal family's input is
    the rows themselves (the routed runner asks every family; a
    block-diffusion one answers with its noised rows)."""
    return np.asarray(tokens, np.int32), None, None


# -- forward -----------------------------------------------------------------

def sees(q_index, k_index, window: int):
    """True where query ``q_index`` sees key ``k_index``: no later key
    and, under a ``window``, its own and the ``window − 1`` before it."""
    ok = k_index <= q_index
    return ok & (k_index > q_index - window) if window else ok


def _attention(q, k, v, window: int):
    """``q [R, T, H, d]`` against ``k, v [R, T, KV, d]``, a block of
    queries against all the keys at a time."""
    r, t, h, d = q.shape
    rep = h // k.shape[2]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    qb = min(QUERY_BLOCK, t)
    k_index = jnp.arange(t)

    @jax.checkpoint
    def one(args):
        start, q_blk = args  # q_blk [R, qb, H, d]
        scores = jnp.einsum(
            "rqhd,rkhd->rhqk", q_blk, k, precision=HIGHEST
        ) / np.sqrt(d).astype(np.float32)
        mask = sees((start + jnp.arange(qb))[:, None], k_index[None, :], window)
        probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        return jnp.einsum("rhqk,rkhd->rqhd", probs, v, precision=HIGHEST)

    blocks = q.reshape(r, t // qb, qb, h, d).transpose(1, 0, 2, 3, 4)
    out = jax.lax.map(one, (jnp.arange(0, t, qb), blocks))
    return out.transpose(1, 0, 2, 3, 4).reshape(r, t, h * d)


def route(u, router, top_k: int):
    """The ``top_k`` largest router logits and a softmax over them alone:
    expert ids and gates ``[T, top_k]``."""
    logits, experts = jax.lax.top_k(jnp.matmul(u, router, precision=HIGHEST), top_k)
    return experts, jax.nn.softmax(logits, axis=-1)


def _experts(h, experts, gates, p, s, cast):
    """The held experts' part of the mixture for ``h [T, D]``, one
    expert after another over all the tokens."""

    @jax.checkpoint
    def one(y, args):
        e, w1, w3, w2 = args
        gate = jnp.sum(jnp.where(experts == e, gates, 0.0), axis=-1)
        up = jax.nn.relu(_matmul(h, w1, cast)) * _matmul(h, w3, cast)
        return y + gate[:, None] * _matmul(up, w2, cast), None

    ids = s["first"] + jnp.arange(s["held"])
    y, _ = jax.lax.scan(
        one, jnp.zeros_like(h),
        (ids, p["w1"]["kernel"], p["w3"]["kernel"], p["w2"]["kernel"]),
    )
    return y


def _layer(x, p, positions, s, cast, windowed: bool, rope: bool):
    r, t, d = x.shape
    x_in = x
    u = _rms_norm(x, p["ln1"]["scale"], s["eps"])
    at = p["attn"]
    q = _matmul(u, at["q"]["kernel"], cast).reshape(r, t, s["heads"], s["hd"])
    k = _matmul(u, at["k"]["kernel"], cast).reshape(r, t, s["kv"], s["hd"])
    v = _matmul(u, at["v"]["kernel"], cast).reshape(r, t, s["kv"], s["hd"])
    if rope:
        q, k = _rope(q, positions, s["theta"]), _rope(k, positions, s["theta"])
    if cast is not None:
        q, k, v = cast(q, -1), cast(k, -1), cast(v, -1)
    o = _attention(q, k, v, s["window"] if windowed else 0)
    x = x + _matmul(o, at["o"]["kernel"], cast)
    h = _rms_norm(x, p["ln2"]["scale"], s["eps"])
    # "ln1" is what the configuration assumes; the others are the hand
    # controls' (the layer's input before its norm, the experts' input)
    read = {"ln1": u, "ln2": h, "x": x_in}[s["router_input"]]
    experts, gates = route(
        read.reshape(r * t, d), p["mlp"]["router"]["kernel"], s["top_k"]
    )
    y = _experts(h.reshape(r * t, d), experts, gates, p["mlp"], s, cast)
    return x + y.reshape(r, t, d), experts


def forward(params: dict, tokens, cfg: dict, cast=None):
    """``[R, T]`` tokens -> float32 logits ``[R, T, vocab]`` and the
    experts each position chose, ``[layers, R·T, top_k]``."""
    s = sizes(cfg)
    positions = jnp.arange(tokens.shape[1])
    x = params["tok_embed"][tokens]
    chosen = []
    for i in range(s["layers"]):
        layer = jax.checkpoint(functools.partial(
            _layer, s=s, cast=cast, windowed=s["windowed"][i], rope=s["rope"][i]
        ))
        x, experts = layer(x, params[f"block{i}"], positions)
        chosen.append(experts)
    x = _rms_norm(x, params["ln_final"]["scale"], s["eps"])
    return _matmul(x, params["head"]["kernel"], cast), jnp.stack(chosen)


def token_loss(params, tokens, labels, cfg, cast=None):
    """Mean next-token cross-entropy over every position."""
    logits, _ = forward(params, tokens, cfg, cast)
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - picked)


def held_pairs(experts, cfg: dict):
    """Of the choices ``[layers, T, top_k]``, how many fall on held
    experts, a layer (their mean)."""
    s = sizes(cfg)
    on = (experts >= s["first"]) & (experts < s["first"] + s["held"])
    return jnp.mean(jnp.sum(on, axis=(1, 2)).astype(jnp.float32))


# -- training ----------------------------------------------------------------

def train_reference(params, batches, cfg: dict, opt: dict, *,
                    cast=None, rows_per_block: int = 1,
                    keep_rows: Optional[slice] = None,
                    freeze: bool = False) -> dict:
    """Follow ``len(batches)`` AdamW steps from ``params`` over
    ``(tokens [R, T], labels [R, T])`` batches, ``rows_per_block`` rows
    at a time with the block gradients averaged. Returns each step's
    loss, the norms of the first gradient and of the parameters' change
    after the last step, over the leaves of the comparison (the ``sdar``
    family's: the experts' kernels of all the layers are one leaf, the
    routers' another, since a choice that flips moves a token's whole
    gradient from one expert to another). ``keep_rows`` and ``freeze``
    plant the faults of the benchmark's tests. State as there: sums and
    updates in place, the moments and the first parameters on the host
    while a gradient is computed."""
    grad_fn = jax.jit(jax.value_and_grad(
        lambda p, x, y: token_loss(p, x, y, cfg, cast)
    ))
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b), donate_argnums=(0,))
    scale = jax.jit(lambda a, s: jax.tree.map(lambda v: v * s, a), donate_argnums=(0,))
    step = jax.jit(
        functools.partial(adamw_step, opt=opt), static_argnums=(4,),
        donate_argnums=(0, 2, 3),
    )
    p0 = jax.device_get(params)
    mu = nu = None
    losses, g1 = [], None
    for i, (x, y) in enumerate(batches):
        x, y = np.asarray(x), np.asarray(y)
        if keep_rows is not None:
            x, y = x[keep_rows], y[keep_rows]
        n = x.shape[0]
        if n % rows_per_block:
            raise ValueError(f"{n} rows do not divide into blocks of {rows_per_block}")
        total, loss = None, 0.0
        for s in range(0, n, rows_per_block):
            rows = slice(s, s + rows_per_block)
            l, g = grad_fn(params, x[rows], y[rows])
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

def live_pairs(length: int, window: int = 0) -> float:
    """(query, key) pairs a row of ``length`` tokens has under the causal
    rule: the triangle, or under a ``window`` shorter than the row the
    band (the first ``window`` queries see a triangle, each later one
    ``window`` keys)."""
    if not window or window >= length:
        return length * (length + 1) / 2.0
    return window * (window + 1) / 2.0 + float(length - window) * window


def layer_kinds(cfg: dict) -> Dict[str, int]:
    """How many of the held layers are window layers, and how many full."""
    windowed = sizes(cfg)["windowed"]
    return {"window": sum(windowed), "full": len(windowed) - sum(windowed)}


def _per_position(cfg: dict) -> float:
    """Multiply-adds a position passes through in a layer, outside the
    attention core: projections, router, and the expected share of its
    ``top_k`` experts that is held."""
    s = sizes(cfg)
    proj = s["d"] * s["hd"] * (2 * s["heads"] + 2 * s["kv"])
    expected = s["top_k"] * s["held"] / s["experts"] * 3 * s["d"] * s["f"]
    return proj + s["d"] * s["experts"] + expected


def _core_pairs(cfg: dict, length: int) -> Dict[str, float]:
    """Live pairs a row, summed over the held layers of each kind."""
    kinds, window = layer_kinds(cfg), sizes(cfg)["window"]
    return {
        "window": kinds["window"] * live_pairs(length, window),
        "full": kinds["full"] * live_pairs(length),
    }


def forward_flops(cfg: dict, length: int) -> float:
    """One row's forward pass: ``length`` positions through the layers,
    the live pairs once a layer (``QKᵀ`` and ``PV``: 4·d a pair a head;
    the band in window layers, the triangle in full ones), the head. No
    recomputation, no padding, no tile outside the mask."""
    s = sizes(cfg)
    core = 4.0 * s["hd"] * s["heads"] * sum(_core_pairs(cfg, length).values())
    return (
        s["layers"] * 2.0 * _per_position(cfg) * length + core
        + 2.0 * s["d"] * s["vocab"] * length
    )


def train_flops_per_sequence(cfg: dict, length: int) -> float:
    """Forward plus backward (twice the forward) for one row."""
    return 3.0 * forward_flops(cfg, length)


def _core_cost(cfg: dict, length: int, rows: float, kinds) -> Dict[str, float]:
    """The least a step's attention cores of ``kinds`` need, forward and
    backward: 4·d a live pair a head forward and twice that backward;
    bytes with q, k, v and the output once forward, those and the
    output's cotangent read and the three gradients written backward, in
    the compute type (a group's seven query heads read one key head:
    its keys and values count once)."""
    s = sizes(cfg)
    wide, narrow = s["heads"] * s["hd"], s["kv"] * s["hd"]
    per_position = (2 * wide + 2 * narrow) + (3 * wide + 2 * narrow) + (wide + 2 * narrow)
    pairs = _core_pairs(cfg, length)
    return {
        "flops": 3.0 * 4.0 * s["hd"] * s["heads"] * rows * sum(pairs[k] for k in kinds),
        "bytes": 2.0 * per_position * length * rows
                 * sum(layer_kinds(cfg)[k] for k in kinds),
    }


def attn_core_cost(cfg: dict, length: int, rows: float) -> Dict[str, float]:
    return _core_cost(cfg, length, rows, ("window", "full"))


def attn_window_cost(cfg: dict, length: int, rows: float) -> Dict[str, float]:
    return _core_cost(cfg, length, rows, ("window",))


def attn_full_cost(cfg: dict, length: int, rows: float) -> Dict[str, float]:
    return _core_cost(cfg, length, rows, ("full",))


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
