"""Plain reference for the ``gpt2`` family: GPT-2's block in float32.

Straight ``jax.numpy``: learned positions, pre-norm blocks (LayerNorm,
fused qkv projection with bias, full causal multi-head attention, output
projection; LayerNorm, GELU(tanh) MLP), final LayerNorm, tied head. No
cache, no kernels, no batching tricks; every matrix product runs at
``precision=HIGHEST`` (a TPU otherwise multiplies float32 in bf16
passes). Imports nothing of the program. The parameter tree it makes is
laid out under the names the program's model reads (``tok_embed``,
``block<i>/attn/qkv/kernel`` ...): that layout is the interface through
which the benchmark hands the same weights to both sides.

Also here, because the yardstick may not live in the program: the
weights made from the seed, the FLOP and byte counts of the family, and
AdamW as the configuration states it.

Where the program departs from the published model and cannot be told
otherwise, the configuration file says so under ``as_run`` and
the reference follows the program there (:func:`as_run`): today
LayerNorm's epsilon, which the program hard-codes at 1e-6 against
GPT-2's 1e-5. A fault of the program, not a cut of the configuration.
"""

from __future__ import annotations

import functools
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


# -- shapes and weights ------------------------------------------------------

def param_shapes(cfg: dict) -> Dict[str, tuple]:
    """Flat ``path -> shape`` of the family's parameters."""
    d, v, t = cfg["n_embd"], cfg["vocab_size"], cfg["n_positions"]
    inner = cfg.get("n_inner") or 4 * d
    out = {"tok_embed": (v, d), "pos_embed": (1, t, d)}
    for i in range(cfg["n_layer"]):
        b = f"block{i}/"
        out.update({
            b + "ln1/scale": (d,), b + "ln1/bias": (d,),
            b + "attn/qkv/kernel": (d, 3 * d), b + "attn/qkv/bias": (3 * d,),
            b + "attn/proj/kernel": (d, d), b + "attn/proj/bias": (d,),
            b + "ln2/scale": (d,), b + "ln2/bias": (d,),
            b + "mlp/fc1/kernel": (d, inner), b + "mlp/fc1/bias": (inner,),
            b + "mlp/fc2/kernel": (inner, d), b + "mlp/fc2/bias": (d,),
        })
    out.update({"ln_final/scale": (d,), "ln_final/bias": (d,)})
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
    """Every weight from the seed, float32, in ONE jitted call on the
    default device. Matrices and embeddings N(0, 0.02) (GPT-2's own
    initializer); biases N(0, 0.02) and norm scales 1 + N(0, 0.1) rather
    than the customary 0 and 1, so that a bias or a scale that one side
    dropped would show in the comparison."""
    shapes = param_shapes(cfg)

    @jax.jit
    def make(key):
        flat = {}
        for i, (path, shape) in enumerate(shapes.items()):
            z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
            if path.endswith("/scale"):
                flat[path] = 1.0 + 0.1 * z
            else:
                flat[path] = 0.02 * z
        return nest(flat)

    return make(seed_key(seed))


# -- lower precisions (the controls) -----------------------------------------

def _ste(x, q):
    """Value of ``q``, gradient of ``x`` (straight-through)."""
    return x + jax.lax.stop_gradient(q - x)


def cast_int8(x, axis):
    """Symmetric int8 with one scale along ``axis`` (the contraction
    axis): per row of an activation, per output channel of a weight."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    return _ste(x, jnp.clip(jnp.round(x / scale), -127, 127) * scale)


CASTS: Dict[str, Optional[Callable]] = {
    "float32": None, "int8": cast_int8,
}


def _matmul(x, w, cast):
    """``x[..., k] @ w[k, n]`` at HIGHEST. Under a control the operands
    are first rounded to its precision, and so are the operands of both
    products of the backward pass (the cotangent with the weight, the
    input with the cotangent): a step computed in that precision, with
    float32 accumulation as the MXU has it."""
    if cast is None:
        return jnp.matmul(x, w, precision=HIGHEST)
    value = lambda t: jax.lax.stop_gradient(t)  # noqa: E731 - casts round only

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

def _layer_norm(x, p, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        np.sqrt(2.0 / np.pi) * (x + 0.044715 * x ** 3)
    ))


def _block(x, p, n_head, eps, cast):
    b, t, d = x.shape
    hd = d // n_head
    y = _layer_norm(x, p["ln1"], eps)
    qkv = _matmul(y, p["attn"]["qkv"]["kernel"], cast) + p["attn"]["qkv"]["bias"]
    qkv = qkv.reshape(b, t, 3, n_head, hd)
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    if cast is not None:
        q, k, v = cast(q, -1), cast(k, -1), cast(v, -1)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST) * hd ** -0.5
    mask = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(mask, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    att = jnp.einsum("bhqk,bkhd->bqhd", probs, v, precision=HIGHEST)
    att = att.reshape(b, t, d)
    x = x + _matmul(att, p["attn"]["proj"]["kernel"], cast) + p["attn"]["proj"]["bias"]
    y = _layer_norm(x, p["ln2"], eps)
    h = _gelu_tanh(_matmul(y, p["mlp"]["fc1"]["kernel"], cast) + p["mlp"]["fc1"]["bias"])
    return x + _matmul(h, p["mlp"]["fc2"]["kernel"], cast) + p["mlp"]["fc2"]["bias"]


def as_run(cfg: dict, key: str):
    """``key`` as it is run: the ``as_run`` group's value where that
    differs from the published one."""
    return cfg.get("as_run", {}).get(key, cfg[key])


def forward(params: dict, tokens, cfg: dict, cast=None):
    """``[B, T]`` int tokens -> ``[B, T, vocab]`` float32 logits."""
    t = tokens.shape[1]
    eps = float(as_run(cfg, "layer_norm_epsilon"))
    x = params["tok_embed"][tokens] + params["pos_embed"][:, :t]
    for i in range(cfg["n_layer"]):
        x = _block(x, params[f"block{i}"], cfg["n_head"], eps, cast)
    x = _layer_norm(x, params["ln_final"], eps)
    return _matmul(x, params["tok_embed"].T, cast)


def token_loss(params, tokens, labels, cfg, cast=None):
    """Mean next-token cross-entropy over every position."""
    logits = forward(params, tokens, cfg, cast)
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - picked)


# -- served tokens -----------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _gap_fn(cfg_key, control: Optional[str]):
    cfg = dict(cfg_key)

    def one(params, row):
        """One padded row ``[T]`` -> for each position p the gap, under
        the float32 logits at p, between the best token and (a) the token
        at p+1 of the row, (b) the token the control's precision puts
        first at p."""
        logits = forward(params, row[None], cfg)[0]
        best = jnp.max(logits, -1)
        nxt = jnp.concatenate([row[1:], row[:1]])
        served = best - jnp.take_along_axis(logits, nxt[:, None], -1)[:, 0]
        if control is None:
            return served, jnp.zeros_like(served)
        low = forward(params, row[None], cfg, CASTS[control])[0]
        pick = jnp.argmax(low, -1)
        return served, best - jnp.take_along_axis(logits, pick[:, None], -1)[:, 0]

    return jax.jit(lambda params, rows: jax.lax.map(
        functools.partial(one, params), rows
    ))


def served_gaps(params, rows: np.ndarray, cfg: dict, control: Optional[str] = None):
    """``rows [K, T]``: prompt then served tokens, zero-padded (causal
    attention keeps the padding out of every earlier position). Returns
    two ``[K, T]`` float32 arrays indexed by position p: the gap of the
    token at p+1, and the gap of the control's own first choice at p.
    One row at a time, so that a [T, vocab] float32 block is the most
    that is live."""
    cfg_key = tuple(sorted(
        (k, v) for k, v in cfg.items() if isinstance(v, (int, float, str))
    ))
    served, ctl = _gap_fn(cfg_key, control)(params, jnp.asarray(rows, jnp.int32))
    return np.asarray(served), np.asarray(ctl)


# -- training ----------------------------------------------------------------

def adamw_step(params, grads, mu, nu, count, opt: dict):
    """AdamW as optax.adamw computes it: bias-corrected moments,
    ``eps`` outside the root, decoupled decay on matrices named
    ``kernel`` only, a constant learning rate."""
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
        mhat = m / (1 - b1 ** count)
        nhat = n / (1 - b2 ** count)
        upd = mhat / (jnp.sqrt(nhat) + eps)
        if path.endswith("/kernel"):
            upd = upd + wd * p
        new_p[path], new_mu[path], new_nu[path] = p - lr * upd, m, n
    return nest(new_p), nest(new_mu), nest(new_nu), count


def comparison_view(tree) -> Dict[str, object]:
    """The leaves as the comparison takes them: the fused ``qkv``
    projection split into its query, key and value thirds. The key's
    bias has no gradient under softmax, and inside the fused leaf it
    would hide behind the other two thirds."""
    out = {}
    for path, leaf in flatten(tree).items():
        if "/attn/qkv/" in path:
            for name, part in zip("qkv", jnp.split(leaf, 3, axis=-1)):
                out[f"{path}.{name}"] = part
        else:
            out[path] = leaf
    return out


@jax.jit
def _norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in comparison_view(tree).items()}


def leaf_norms(tree) -> Dict[str, float]:
    """Per-leaf L2 norms, over :func:`comparison_view`'s leaves."""
    return {k: float(v) for k, v in _norms(tree).items()}


def train_reference(params, batches, cfg: dict, opt: dict, *,
                    cast=None, rows_per_block: int = 2,
                    keep_rows: Optional[slice] = None,
                    freeze: bool = False) -> dict:
    """Follow ``len(batches)`` AdamW steps from ``params``.

    ``batches``: list of ``(tokens [B, T], labels [B, T])``. The batch is
    taken ``rows_per_block`` rows at a time and the block gradients
    averaged, so that float32 activations of a whole batch never have to
    fit. Returns each step's loss, the per-leaf norm of the first
    gradient, and the per-leaf norm of the parameters' change after the
    last step.

    ``keep_rows`` (a slice of the batch) and ``freeze`` plant the faults
    of the benchmark's tests: half the batch left out with the mean over
    the rest, and a step that returns its state unchanged.
    """
    grad_fn = jax.jit(jax.value_and_grad(
        lambda p, x, y: token_loss(p, x, y, cfg, cast)
    ))
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b))
    scale = jax.jit(lambda a, s: jax.tree.map(lambda v: v * s, a))
    step = jax.jit(functools.partial(adamw_step, opt=opt), static_argnums=(4,))
    p0 = params
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
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
            l, g = grad_fn(params, x[s:s + rows_per_block], y[s:s + rows_per_block])
            total = g if total is None else add(total, g)
            loss += float(l)
        k = n // rows_per_block
        grads = scale(total, 1.0 / k)
        losses.append(loss / k)
        if i == 0:
            g1 = leaf_norms(grads)
        if not freeze:
            params, mu, nu, _ = step(params, grads, mu, nu, i)
    delta = jax.jit(lambda a, b: jax.tree.map(jnp.subtract, a, b))(params, p0)
    return {"losses": losses, "grad_norms": g1, "delta_norms": leaf_norms(delta)}


# -- operations and bytes, from shapes ---------------------------------------

def matmul_params(cfg: dict) -> int:
    """Parameters that a token is multiplied through: the block matrices
    and the tied head (the embedding lookup is a gather, not a product)."""
    d = cfg["n_embd"]
    inner = cfg.get("n_inner") or 4 * d
    per_layer = d * 3 * d + d * d + 2 * d * inner
    return cfg["n_layer"] * per_layer + cfg["vocab_size"] * d


def forward_flops(cfg: dict, n_tokens: float, attended: float) -> float:
    """FLOPs of a forward pass over ``n_tokens`` new tokens whose
    queries attend to ``attended`` (query, key) pairs in all: 2 per
    multiply-add through the matrices, and 4·d per pair (QK^T and PV)
    per layer."""
    return (2.0 * matmul_params(cfg) * n_tokens
            + 4.0 * cfg["n_embd"] * cfg["n_layer"] * attended)


def causal_pairs(t: int) -> float:
    """(query, key) pairs a causal pass over ``t`` tokens needs."""
    return t * (t + 1) / 2.0


def train_flops_per_sequence(cfg: dict, t: int) -> float:
    """Forward plus backward (twice the forward); recomputation is not
    counted."""
    return 3.0 * forward_flops(cfg, t, causal_pairs(t))


def kv_bytes_per_token(cfg: dict, bytes_per_value: int = 2) -> int:
    return 2 * cfg["n_layer"] * cfg["n_embd"] * bytes_per_value


def weight_bytes(cfg: dict, bytes_per_value: int = 2) -> int:
    """What one decode step has to read of the weights, in the compute
    type the configuration states: every matrix once, and the position
    table's and the norms' few rows (the embedding is read through the
    head)."""
    return bytes_per_value * (param_count(cfg) - cfg["n_positions"] * cfg["n_embd"])


def decode_step_cost(cfg: dict, live_rows: float, live_tokens: float) -> Dict[str, float]:
    """The least one decode step needs for ``live_rows`` sequences that
    hold ``live_tokens`` cached tokens between them: FLOPs, and bytes
    with the weights read once and each live cached token read once."""
    return {
        "flops": forward_flops(cfg, live_rows, live_tokens + live_rows),
        "bytes": float(weight_bytes(cfg) + kv_bytes_per_token(cfg) * live_tokens),
    }
