"""Plain reference for the ``granite`` family: Granite 4.0-H Micro's
layers (Mamba-2 state-space layers beside an attention layer without
positions) trained on next-token prediction, in float32.

Straight ``jax.numpy`` under ``jax.default_matmul_precision("highest")``
with every matrix product at ``precision=HIGHEST`` besides, no kernels,
**no chunks**: the state-space layer is the token-by-token recurrence, a
``lax.scan`` over positions that carries ``S [H, P, N]``, so that the
program's chunked algebra (``ops/ssm.py``) is compared with something
that shares none of it. Imports nothing of the program; the tree
helpers, the lower-precision product of the controls and AdamW are the
``sdar`` reference's family-blind parts. The parameter tree lies under
the names the program's model reads (``tok_embed``, ``block<i>/ssm/
in_proj/kernel`` ..., ``block<i>/attn/q/kernel`` ..., ``block<i>/mlp/
w_in/kernel``): that layout is the interface through which the
benchmark hands the same weights to both sides.

**The model** (``config.json`` of ibm-granite/granite-4.0-h-micro,
``granitemoehybrid``; the Mamba-2 layer is arXiv:2405.21060's): ``x_0 =
12 · E[token]`` (``embedding_multiplier``); every layer ``u =
RMSNorm(x)``, ``x <- x + 0.22 · mixer(u)``, ``v = RMSNorm(x)``, ``[p |
q] = v·W_in`` (2 x 8,192), ``x <- x + 0.22 · (SiLU(p) ⊙ q)·W_out``
(``residual_multiplier``); a final RMSNorm; ``logits = x·Eᵀ / 8``
(tied, ``logits_scaling``). ε = 1e-5, no bias, no positions anywhere.

* *Attention mixer* (``layer_types[l] == "attention"``): 32 query and 8
  key heads of 64, scores times 1/64 (``attention_multiplier``, not
  1/√64), causal softmax, output projection.
* *Mamba-2 mixer* (``"mamba"``): ``[z | xBC | dt] = u·W_in`` (4,096 |
  4,352 | 64); ``xBC <- SiLU(b_c + Σ_j w_{c,j} · xBC_{t−3+j,c})`` (causal,
  depthwise, noughts before the row's start); ``[xs | B | C]`` = 4,096 |
  128 | 128, ``xs`` as 64 heads of 64; ``Δ = softplus(dt + dt_bias)``,
  ``a = −exp(A_log)``; ``S_t = exp(Δ_t a) S_{t−1} + Δ_t xs_t ⊗ B_t``,
  ``y_t = S_t·C_t + D xs_t``, ``S_0 = 0``; ``g = y ⊙ SiLU(z)`` (the gate
  first), ``n = g · rsqrt(mean_4096(g²) + ε) ⊙ w``; out ``n·W_out``.

The loss is the mean next-token cross-entropy over every position.

**Departures and what is assumed** (the configuration file lists the
same under ``assumed``): the equations the config has no key for (the
taps' order, the gate before the norm, one norm group, softplus on ``dt
+ dt_bias`` with no clamp) are the modelling code's from memory; the
head width is ``hidden_size / num_attention_heads``; the vocabulary is
the slice's; weights random from the seed (:func:`init_params`). The
positions are walked in stretches of :data:`STRETCH` under
``jax.checkpoint`` (a scan over 4,096 positions would keep 4,096 states
of 2 MiB for its backward pass), queries meet the keys a block at a
time, and layers are recomputed in the backward pass: none of it changes
a number. ``cfg["without"]`` (a list: ``"decay"``, ``"conv"``,
``"gate_first"``) takes one mechanism out, for the hand controls and the
tests that the comparison sees each.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.references.sdar import (  # noqa: F401  (CASTS: the runner's)
    CASTS,
    HIGHEST,
    _matmul,
    _rms_norm,
    _sums_of_squares,
    adamw_step,
    flatten,
    nest,
    seed_key,
)

QUERY_BLOCK = 512  # queries that meet all the keys at once
STRETCH = 64  # positions of the recurrence between two kept states


# -- the configuration as the reference reads it -----------------------------

def sizes(cfg: dict) -> dict:
    layers = cfg["layers"]
    return {
        "d": cfg["hidden_size"], "layers": layers,
        "heads": cfg["num_attention_heads"], "kv": cfg["num_key_value_heads"],
        "hd": cfg["hidden_size"] // cfg["num_attention_heads"],
        "f": cfg["shared_intermediate_size"], "vocab": cfg["vocab_size"],
        "eps": cfg["rms_norm_eps"],
        "mamba": tuple(t == "mamba" for t in cfg["layer_types"][:layers]),
        "H": cfg["mamba_n_heads"], "P": cfg["mamba_d_head"],
        "N": cfg["mamba_d_state"], "G": cfg["mamba_n_groups"],
        "K": cfg["mamba_d_conv"], "chunk": cfg["mamba_chunk_size"],
        "embed": float(cfg["embedding_multiplier"]),
        "attn": float(cfg["attention_multiplier"]),
        "residual": float(cfg["residual_multiplier"]),
        "logits": float(cfg["logits_scaling"]),
        "without": tuple(cfg.get("without", ())),
    }


# -- shapes and weights ------------------------------------------------------

def param_shapes(cfg: dict) -> Dict[str, tuple]:
    """Flat ``path -> shape`` of the family's parameters."""
    s = sizes(cfg)
    d, f = s["d"], s["f"]
    inner, bc = s["H"] * s["P"], s["G"] * s["N"]
    out = {"tok_embed": (s["vocab"], d)}
    for i in range(s["layers"]):
        b = f"block{i}/"
        out[b + "ln1/scale"] = (d,)
        if s["mamba"][i]:
            out.update({
                b + "ssm/in_proj/kernel": (d, 2 * inner + 2 * bc + s["H"]),
                b + "ssm/conv/kernel": (inner + 2 * bc, s["K"]),
                b + "ssm/conv/bias": (inner + 2 * bc,),
                b + "ssm/dt_bias": (s["H"],),
                b + "ssm/A_log": (s["H"],),
                b + "ssm/D": (s["H"],),
                b + "ssm/norm/scale": (inner,),
                b + "ssm/out_proj/kernel": (inner, d),
            })
        else:
            out.update({
                b + "attn/q/kernel": (d, s["heads"] * s["hd"]),
                b + "attn/k/kernel": (d, s["kv"] * s["hd"]),
                b + "attn/v/kernel": (d, s["kv"] * s["hd"]),
                b + "attn/o/kernel": (s["heads"] * s["hd"], d),
            })
        out.update({
            b + "ln2/scale": (d,),
            b + "mlp/w_in/kernel": (d, 2 * f),
            b + "mlp/w_out/kernel": (f, d),
        })
    out["ln_final/scale"] = (d,)
    return out


def param_count(cfg: dict) -> int:
    return int(sum(np.prod(s) for s in param_shapes(cfg).values()))


def init_params(cfg: dict, seed: int) -> dict:
    """Every weight from the seed, float32, in one jitted call. Matrices
    and the embedding N(0, 0.02) (the embedding is the head too, and the
    model multiplies it by 12 on the way in); what writes into the
    residual stream (``out_proj``, attention's ``o``, the MLP's
    ``w_out``) scaled by 1/sqrt(2 x published layers), as GPT-2
    initialises them; norm scales 1 + N(0, 0.1), so that a scale one side
    dropped would show. The state-space layer's own as Mamba-2 publishes
    them: ``A_log = log U(1, 16)``, ``dt_bias`` the inverse softplus of a
    step drawn log-uniform in [1e-3, 1e-1], ``D`` = 1, the taps and their
    bias uniform in ±1/sqrt(K) (a depthwise ``Conv1d``'s default)."""
    residual = (2.0 * cfg["published"]["layers"]) ** -0.5
    shapes = param_shapes(cfg)
    taps = float(cfg["mamba_d_conv"]) ** -0.5

    @jax.jit
    def make(key):
        flat = {}
        for i, (path, shape) in enumerate(shapes.items()):
            k = jax.random.fold_in(key, i)
            if path.endswith("/A_log"):
                flat[path] = jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
            elif path.endswith("/dt_bias"):
                dt = jnp.exp(jax.random.uniform(
                    k, shape, jnp.float32, np.log(1e-3), np.log(1e-1)
                ))
                flat[path] = dt + jnp.log(-jnp.expm1(-dt))
            elif path.endswith("/D"):
                flat[path] = jnp.ones(shape, jnp.float32)
            elif "/conv/" in path:
                flat[path] = jax.random.uniform(k, shape, jnp.float32, -taps, taps)
            else:
                z = jax.random.normal(k, shape, jnp.float32)
                if path.endswith("/scale"):
                    flat[path] = 1.0 + 0.1 * z
                elif path.endswith(("out_proj/kernel", "attn/o/kernel", "w_out/kernel")):
                    flat[path] = 0.02 * residual * z
                else:
                    flat[path] = 0.02 * z
        return nest(flat)

    return make(seed_key(seed))


def leaf_norms(tree) -> Dict[str, float]:
    """L2 norm of every parameter's leaf, each compared for itself (no
    layer here routes, so nothing is pooled)."""
    return {k: float(np.sqrt(v)) for k, v in _sums_of_squares(tree).items()}


# -- forward -----------------------------------------------------------------

def _attention(q, k, v, scale: float):
    """``q [R, T, H, d]`` against ``k, v [R, T, KV, d]`` under the causal
    mask, a block of queries against all the keys at a time."""
    r, t, h, d = q.shape
    rep = h // k.shape[2]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    qb = min(QUERY_BLOCK, t)
    if t % qb:
        qb = t
    k_index = jnp.arange(t)

    @jax.checkpoint
    def one(args):
        start, q_blk = args  # q_blk [R, qb, H, d]
        scores = jnp.einsum("rqhd,rkhd->rhqk", q_blk, k, precision=HIGHEST) * scale
        mask = k_index[None, :] <= (start + jnp.arange(qb))[:, None]
        probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        return jnp.einsum("rhqk,rkhd->rqhd", probs, v, precision=HIGHEST)

    blocks = q.reshape(r, t // qb, qb, h, d).transpose(1, 0, 2, 3, 4)
    out = jax.lax.map(one, (jnp.arange(0, t, qb), blocks))
    return out.transpose(1, 0, 2, 3, 4).reshape(r, t, h * d)


def causal_conv(x, w, bias):
    """``y[t, c] = bias[c] + Σ_j w[c, j] · x[t − (K−1) + j, c]`` over ``x
    [R, T, C]``, noughts before the row's start."""
    taps, t = w.shape[1], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return bias + sum(w[:, j] * padded[:, j:j + t] for j in range(taps))


def recurrence(xs, dt, a, b, c, d, decay: bool = True):
    """``S_t = exp(Δ_t a) S_{t−1} + Δ_t xs_t ⊗ B_t``, ``y_t = S_t·C_t + D
    xs_t``, a position at a time: ``xs [R, T, H, P]``, ``dt [R, T, H]``,
    ``a, d [H]``, ``b, c [R, T, G, N]`` -> ``[R, T, H, P]``."""
    r, t, h, p = xs.shape
    rep = h // b.shape[2]
    b, c = jnp.repeat(b, rep, axis=2), jnp.repeat(c, rep, axis=2)

    def step(state, at):
        x, delta, b_t, c_t = at  # [R,H,P], [R,H], [R,H,N], [R,H,N]
        if decay:
            state = jnp.exp(delta * a)[..., None, None] * state
        state = state + (delta[..., None] * x)[..., None] * b_t[:, :, None, :]
        y = jnp.einsum("rhpn,rhn->rhp", state, c_t, precision=HIGHEST)
        return state, y + d[:, None] * x

    stretch = STRETCH if t % STRETCH == 0 else t

    @jax.checkpoint
    def walk(state, stretch_of):
        return jax.lax.scan(step, state, stretch_of)

    def by_stretch(v):  # [R, T, ...] -> [T/stretch, stretch, R, ...]
        v = jnp.moveaxis(v, 1, 0)
        return v.reshape((t // stretch, stretch) + v.shape[1:])

    first = jnp.zeros((r, h, p, b.shape[3]), jnp.float32)
    _, y = jax.lax.scan(walk, first, tuple(by_stretch(v) for v in (xs, dt, b, c)))
    return jnp.moveaxis(y.reshape((t,) + y.shape[2:]), 0, 1)


def _mamba(u, p, s, cast):
    r, t, _ = u.shape
    inner, bc = s["H"] * s["P"], s["G"] * s["N"]
    z, xbc, dt = jnp.split(
        _matmul(u, p["in_proj"]["kernel"], cast), [inner, 2 * inner + 2 * bc], axis=-1
    )
    if "conv" not in s["without"]:
        xbc = causal_conv(xbc, p["conv"]["kernel"], p["conv"]["bias"])
    xbc = jax.nn.silu(xbc)
    xs, b, c = jnp.split(xbc, [inner, inner + bc], axis=-1)
    y = recurrence(
        xs.reshape(r, t, s["H"], s["P"]),
        jax.nn.softplus(dt + p["dt_bias"]), -jnp.exp(p["A_log"]),
        b.reshape(r, t, s["G"], s["N"]), c.reshape(r, t, s["G"], s["N"]),
        p["D"], decay="decay" not in s["without"],
    ).reshape(r, t, inner)
    if "gate_first" in s["without"]:  # the norm first, then the gate
        n = _rms_norm(y, p["norm"]["scale"], s["eps"]) * jax.nn.silu(z)
    else:
        n = _rms_norm(y * jax.nn.silu(z), p["norm"]["scale"], s["eps"])
    return _matmul(n, p["out_proj"]["kernel"], cast)


def _attn(u, p, s, cast):
    r, t, _ = u.shape
    q = _matmul(u, p["q"]["kernel"], cast).reshape(r, t, s["heads"], s["hd"])
    k = _matmul(u, p["k"]["kernel"], cast).reshape(r, t, s["kv"], s["hd"])
    v = _matmul(u, p["v"]["kernel"], cast).reshape(r, t, s["kv"], s["hd"])
    if cast is not None:
        q, k, v = cast(q, -1), cast(k, -1), cast(v, -1)
    return _matmul(_attention(q, k, v, s["attn"]), p["o"]["kernel"], cast)


def _layer(x, p, s, cast, mamba: bool):
    u = _rms_norm(x, p["ln1"]["scale"], s["eps"])
    mixed = _mamba(u, p["ssm"], s, cast) if mamba else _attn(u, p["attn"], s, cast)
    x = x + s["residual"] * mixed
    v = _rms_norm(x, p["ln2"]["scale"], s["eps"])
    pq = _matmul(v, p["mlp"]["w_in"]["kernel"], cast)
    up = jax.nn.silu(pq[..., :s["f"]]) * pq[..., s["f"]:]
    return x + s["residual"] * _matmul(up, p["mlp"]["w_out"]["kernel"], cast)


def forward(params: dict, tokens, cfg: dict, cast=None):
    """``[R, T]`` tokens -> float32 logits ``[R, T, vocab]``."""
    s = sizes(cfg)
    with jax.default_matmul_precision("highest"):
        x = s["embed"] * params["tok_embed"][tokens]
        for i in range(s["layers"]):
            layer = jax.checkpoint(functools.partial(
                _layer, s=s, cast=cast, mamba=s["mamba"][i]
            ))
            x = layer(x, params[f"block{i}"])
        x = _rms_norm(x, params["ln_final"]["scale"], s["eps"])
        return _matmul(x, params["tok_embed"].T, cast) / s["logits"]


def token_loss(params, tokens, labels, cfg, cast=None):
    """Mean next-token cross-entropy over every position."""
    logits = forward(params, tokens, cfg, cast)
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - picked)


# -- training ----------------------------------------------------------------

def train_reference(params, batches, cfg: dict, opt: dict, *,
                    cast=None, rows_per_block: int = 1,
                    keep_rows: Optional[slice] = None,
                    freeze: bool = False) -> dict:
    """Follow ``len(batches)`` AdamW steps from ``params`` over
    ``(tokens [R, T], labels [R, T])`` batches, ``rows_per_block`` rows
    at a time with the block gradients averaged. Returns each step's
    loss, the per-leaf norm of the first gradient and of the parameters'
    change after the last step. ``keep_rows`` and ``freeze`` plant the
    faults of the benchmark's tests. The float32 state is 11.5 GiB at
    0.77B parameters: Adam's moments and the first parameters wait on
    the host, sums are made in place, and the update is made a top-level
    subtree (a layer) at a time, so that beside the parameters and the
    gradient only one layer's moments are on the device."""
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
        for r in range(0, n, rows_per_block):
            rows = slice(r, r + rows_per_block)
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
            params, grads = dict(params), dict(grads)
            for top in list(params):
                part = lambda tree: {top: tree[top]}  # noqa: E731
                new_p, new_mu, new_nu, _ = step(
                    part(params), {top: grads.pop(top)},
                    jax.device_put(part(mu)), jax.device_put(part(nu)), i,
                )
                params[top] = new_p[top]
                mu[top], nu[top] = jax.device_get((new_mu[top], new_nu[top]))
            del grads
    first = flatten(p0)
    delta = {
        k: float(jnp.sqrt(jnp.sum(jnp.square(v - jnp.asarray(first[k])))))
        for k, v in flatten(params).items()
    }
    return {"losses": losses, "grad_norms": g1, "delta_norms": delta}


# -- operations and bytes, from shapes ---------------------------------------

def layer_kinds(cfg: dict) -> Dict[str, int]:
    """How many of the held layers are state-space layers, and how many
    attention layers."""
    mamba = sizes(cfg)["mamba"]
    return {"mamba": sum(mamba), "attention": len(mamba) - sum(mamba)}


def live_pairs(length: int) -> float:
    """(query, key) pairs under the causal rule, and (t, s) pairs of a
    chunk with ``s <= t``: the triangle with its diagonal."""
    return length * (length + 1) / 2.0


def scan_flops(cfg: dict, length: int) -> float:
    """One state-space layer's scan over one row, forward: the four
    products of the chunked form at the configuration's chunk, whatever
    implements them. A chunk of ``Q``: ``C·Bᵀ`` and ``(C·Bᵀ ⊙ L)·(Δ ⊙
    xs)`` over the ``Q(Q+1)/2`` pairs with ``s <= t`` (2·N a pair a group,
    2·P a pair a head), the chunk's own state and the carried state's
    reading, 2·P·N a position a head each. A last chunk that is ragged is
    counted at its own length; no padding."""
    s = sizes(cfg)
    q = s["chunk"]
    whole, rest = divmod(length, q)
    pairs = whole * live_pairs(q) + live_pairs(rest)
    return (
        pairs * (2.0 * s["N"] * s["G"] + 2.0 * s["P"] * s["H"])
        + length * 2 * 2.0 * s["P"] * s["N"] * s["H"]
    )


def _per_position(cfg: dict) -> Dict[str, float]:
    """Multiply-adds a position passes through in a layer's matrix
    products, by the layer's kind (the MLP in both)."""
    s = sizes(cfg)
    inner, bc = s["H"] * s["P"], s["G"] * s["N"]
    mlp = 3 * s["d"] * s["f"]
    return {
        "mamba": s["d"] * (2 * inner + 2 * bc + s["H"]) + inner * s["d"] + mlp,
        "attention": s["d"] * s["hd"] * (2 * s["heads"] + 2 * s["kv"]) + mlp,
    }


def forward_flops(cfg: dict, length: int) -> float:
    """One row's forward pass: the matrix products of every layer, the
    attention layers' causal pairs once (``QKᵀ`` and ``PV``: 4·d a pair a
    head), the state-space layers' scans (:func:`scan_flops`), the tied
    head. No recomputation, no padding; the convolution, the norms and
    the gates are not products and are not counted."""
    s = sizes(cfg)
    kinds, per = layer_kinds(cfg), _per_position(cfg)
    return (
        2.0 * length * sum(kinds[k] * per[k] for k in kinds)
        + kinds["attention"] * 4.0 * s["hd"] * s["heads"] * live_pairs(length)
        + kinds["mamba"] * scan_flops(cfg, length)
        + 2.0 * s["d"] * s["vocab"] * length
    )


def train_flops_per_sequence(cfg: dict, length: int) -> float:
    """Forward plus backward (twice the forward) for one row."""
    return 3.0 * forward_flops(cfg, length)


def ssm_scan_cost(cfg: dict, length: int, rows: float) -> Dict[str, float]:
    """The least a step's scans need, all state-space layers, forward
    and backward: :func:`scan_flops` forward and twice that backward;
    bytes with ``xs``, ``B``, ``C`` and ``Δ`` read and ``y`` written once
    forward, and backward those read again with ``y``'s cotangent and
    their four cotangents written, in the compute type. Fixed by the
    configuration's chunk, whatever implements the scan."""
    s = sizes(cfg)
    operands = s["H"] * s["P"] + 2 * s["G"] * s["N"] + s["H"]  # xs, B, C, Δ a position
    out = s["H"] * s["P"]
    per_position = (operands + out) + (operands + out + operands)
    layers = layer_kinds(cfg)["mamba"]
    return {
        "flops": 3.0 * scan_flops(cfg, length) * rows * layers,
        "bytes": 2.0 * per_position * length * rows * layers,
    }


def attn_core_cost(cfg: dict, length: int, rows: float) -> Dict[str, float]:
    """The least a step's attention cores need, forward and backward:
    4·d a causal pair a head forward and twice that backward; bytes with
    q, k, v and the output once forward, those and the output's
    cotangent read and the three gradients written backward, in the
    compute type (a group's four query heads read one key head: its keys
    and values count once)."""
    s = sizes(cfg)
    wide, narrow = s["heads"] * s["hd"], s["kv"] * s["hd"]
    per_position = (2 * wide + 2 * narrow) + (3 * wide + 2 * narrow) + (wide + 2 * narrow)
    layers = layer_kinds(cfg)["attention"]
    return {
        "flops": 3.0 * 4.0 * s["hd"] * s["heads"] * live_pairs(length) * rows * layers,
        "bytes": 2.0 * per_position * length * rows * layers,
    }
