"""Slot-pool batched decode engine — the compiled heart of serving.

One pooled KV cache of ``[num_slots, max_len, heads, head_dim]`` rows
per attention layer, and exactly **bucket_count + 1 compiled programs**
for the engine's whole lifetime:

* one *decode step*: every occupied slot advances one token — per-slot
  positions (vector ``cache_index``/``pos_index``, see
  ``models/vit.Attention._decode_attention``), per-slot sampling config
  as data (``serving.sampling``), per-slot stop detection on device.
  Requests join and leave between steps; the program never changes.
* one *prefill* per prompt-length bucket: the prompt padded up the
  bucket ladder runs one full causal forward with a fresh zero cache
  and writes K/V straight into the assigned slot's pool rows
  (``dynamic_update_slice`` at the slot index — the padded tail beyond
  ``prompt_len`` lands in rows the decode mask can never attend before
  they are overwritten, so it needs no cleanup). The first token is
  sampled inside the program from the true last prompt position.

Static shapes everywhere; admission, eviction and any greedy/sampled
request mix are pure data. Both programs are AOT-compiled
(``.lower().compile()``, cache pool donated) at :meth:`SlotEngine.warmup`
— after it, the engine *cannot* recompile, which
``tests/test_serving.py`` pins with a backend-compile listener across
an admission/eviction churn.

Bitwise contract: each request's token stream equals sequential
``inference.generate`` (same prompt, config and rng) — the per-request
key ladder is precomputed on the host (``serving.keys``) and fed per
step, so co-scheduling cannot perturb any request's randomness.
"""

from __future__ import annotations

import concurrent.futures
import dataclasses
import os
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from distributeddeeplearning_tpu import obs
from distributeddeeplearning_tpu.obs import programs as obs_programs
from distributeddeeplearning_tpu.serving import keys as keylib
from distributeddeeplearning_tpu.serving.blocks import (
    BlockAllocator,
    BlockPoolExhausted,
)
from distributeddeeplearning_tpu.serving.sampling import (
    DEFAULT_TOP_K_CAP,
    sample_slot,
    sample_slots,
    spec_verify_slots,
)
from distributeddeeplearning_tpu.serving.spec import (
    NgramDrafter,
    propose_all,
    validate_spec_config,
)
from distributeddeeplearning_tpu.utils.logging import get_logger

_INDEX_NAMES = ("cache_index", "pos_index")
# Paged layout (kv_layout="paged"): the block pools are batch-independent
# shared tensors; the block table is per-row routing data fed each step
# exactly like the position vectors. The *_scale pools exist only under
# kv_dtype="int8" (f32 scales resident beside the int8 payload) and
# follow the same block addressing.
_PAGED_POOL_NAMES = ("paged_k", "paged_v", "paged_k_scale", "paged_v_scale")
_TABLE_NAME = "block_table"


@dataclasses.dataclass
class ProgramSpec:
    """One member of the engine's closed program set — everything needed
    to compile it (:meth:`SlotEngine.warmup`) or to lower it for
    inspection (the ddlint HLO audit, ``analysis/hlo_audit.py``). Both
    consumers iterate the SAME table (:meth:`SlotEngine.program_specs`),
    so what the lint audits is, by construction, what serves."""

    name: str
    fn: Callable
    donate_argnums: Tuple[int, ...]
    example_args: tuple
    span: Dict[str, Any]  # labels for the `compile` span
    _get: Callable[[], Any]  # read the installed executable slot
    _set: Callable[[Any], None]  # install a compiled executable

    @property
    def installed(self) -> bool:
        return self._get() is not None

    def install(self, compiled: Any) -> None:
        self._set(compiled)


def default_buckets(max_len: int, smallest: int = 16) -> Tuple[int, ...]:
    """Power-of-two prefill ladder up to ``max_len`` (always including
    ``max_len`` itself so any admissible prompt has a bucket)."""
    out: List[int] = []
    b = smallest
    while b < max_len:
        out.append(b)
        b *= 2
    out.append(max_len)
    return tuple(sorted(set(out)))


@dataclasses.dataclass
class ReqSpec:
    """One request's generation spec — mirrors ``inference.generate``'s
    keyword surface; ``rng`` is raw key data ([2] uint32), an int seed,
    or None (PRNGKey(0), like ``generate``)."""

    prompt: np.ndarray
    max_new_tokens: int
    temperature: float = 0.0
    top_k: Optional[int] = None
    top_p: Optional[float] = None
    eos_token: Optional[int] = None
    rng: Any = None

    def validate(self, max_len: int, max_bucket: int) -> None:
        t = int(np.asarray(self.prompt).shape[-1])
        if np.asarray(self.prompt).ndim != 1 or t < 1:
            raise ValueError("prompt must be a non-empty 1-D token array")
        if self.max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {self.max_new_tokens}"
            )
        if t > max_bucket:
            raise ValueError(
                f"prompt length {t} exceeds the largest prefill bucket "
                f"{max_bucket}"
            )
        if t + self.max_new_tokens > max_len:
            raise ValueError(
                f"prompt {t} + max_new_tokens {self.max_new_tokens} "
                f"exceeds the engine cache length {max_len}"
            )
        if self.top_p is not None and not 0.0 < self.top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {self.top_p}")
        if self.top_k is not None and self.top_k < 1:
            raise ValueError(f"top_k must be >= 1, got {self.top_k}")

    def key_data(self) -> np.ndarray:
        if self.rng is None:
            return keylib.key_from_seed(0)
        if isinstance(self.rng, (int, np.integer)):
            return keylib.key_from_seed(int(self.rng))
        return np.asarray(self.rng, np.uint32).reshape(2)


class SlotEngine:
    """Continuous-batching decode over ``num_slots`` KV-cache slots.

    Low-level and mechanical by design: it owns the device cache pool,
    the compiled programs and per-slot decode bookkeeping. Queueing,
    deadlines and request lifecycles live in
    :class:`~distributeddeeplearning_tpu.serving.scheduler.Server`.
    """

    def __init__(
        self,
        model,
        params,
        *,
        num_slots: int = 8,
        max_len: Optional[int] = None,
        buckets: Optional[Tuple[int, ...]] = None,
        top_k_cap: int = DEFAULT_TOP_K_CAP,
        kv_layout: str = "dense",
        block_size: int = 16,
        num_blocks: Optional[int] = None,
        prefix_cache: bool = True,
        kv_dtype: str = "bf16",
        weight_dtype: str = "bf16",
        decode_kernel: str = "xla",
        spec_k: int = 0,
        spec_draft: str = "int8",
        spec_ngram_n: int = 3,
        pool_role: str = "both",
    ) -> None:
        from distributeddeeplearning_tpu.ops import quant as quantlib
        from distributeddeeplearning_tpu.training.warmup import (
            enable_compile_cache,
        )

        # Every serving compile (Server.build, each fleet Replica) is
        # owned by an engine: place the persistent cache before the
        # first one.
        enable_compile_cache()
        if num_slots < 1:
            raise ValueError(f"num_slots must be >= 1, got {num_slots}")
        if kv_layout not in ("dense", "paged"):
            raise ValueError(
                f"kv_layout must be 'dense' or 'paged', got {kv_layout!r}"
            )
        # Disaggregated serving (docs/SERVING.md): a pool-typed engine
        # compiles only its phase's programs — "prefill" skips the
        # decode step, "decode" skips the prefill ladder — so each pool
        # keeps a smaller closed program set. Pool typing requires the
        # paged layout (the block table is the handoff unit) and no
        # speculation (the draft pool's state does not travel).
        if pool_role not in ("both", "prefill", "decode"):
            raise ValueError(
                f"pool_role must be one of ('both', 'prefill', 'decode'), "
                f"got {pool_role!r}"
            )
        if pool_role != "both":
            if kv_layout != "paged":
                raise ValueError(
                    f"pool_role={pool_role!r} requires kv_layout='paged' "
                    "(the block table is the handoff unit)"
                )
            if spec_k:
                raise ValueError(
                    f"pool_role={pool_role!r} is incompatible with "
                    f"spec_k={spec_k} (draft state does not travel)"
                )
        self.pool_role = pool_role
        # "bf16" means *native* (store the model's compute dtype — the
        # pre-quantization behaviour); "int8"/"fp8" engage ops/quant.py.
        # The supported tiers live in ONE registry (quant.KV_DTYPES /
        # quant.WEIGHT_DTYPES) so the enum, the env parsing (ServeConfig)
        # and this boundary reject unknown dtypes with the same list.
        quantlib.validate_store_dtype("kv_dtype", kv_dtype)
        quantlib.validate_store_dtype("weight_dtype", weight_dtype)
        # fp8 is platform-gated. A backend that cannot round-trip
        # float8 gets an error, not the int8 tier under fp8's name: the
        # caller asked for a storage format and every byte count and
        # parity figure downstream is reported against it.
        if "fp8" in (kv_dtype, weight_dtype) and not quantlib.fp8_supported():
            raise ValueError(
                f"fp8 storage is unsupported on backend "
                f"{jax.default_backend()!r} (kv_dtype={kv_dtype} "
                f"weight_dtype={weight_dtype}); ask for int8 or bf16"
            )
        if decode_kernel not in ("xla", "fused"):
            raise ValueError(
                f"decode_kernel must be one of ('xla', 'fused'), got "
                f"{decode_kernel!r}"
            )
        validate_spec_config(spec_k, spec_draft, spec_ngram_n, weight_dtype)
        model_max = getattr(model, "max_seq_len", None)
        if max_len is None:
            if model_max is None:
                raise ValueError("max_len required for models without "
                                 "max_seq_len")
            max_len = int(model_max)
        if model_max is not None and max_len > model_max:
            raise ValueError(
                f"max_len {max_len} exceeds model.max_seq_len {model_max}"
            )
        from distributeddeeplearning_tpu.inference import decode_variant

        self.model = model
        self.num_slots = int(num_slots)
        self.max_len = int(max_len)
        self.kv_layout = kv_layout
        self.kv_dtype = kv_dtype
        self.weight_dtype = weight_dtype
        self.decode_kernel = decode_kernel
        self.allocator: Optional[BlockAllocator] = None
        self.prefix_cache = bool(prefix_cache) and kv_layout == "paged"
        quant_kw = dict(kv_dtype=kv_dtype) if kv_dtype != "bf16" else {}
        # The kernel knob changes the decode programs' LOWERING, not the
        # program set: decode_variant threads it into the model clone and
        # vit.Attention dispatches the vector-position decode paths to
        # the fused Pallas kernel (ops/pallas/paged_decode.py). The
        # draft model below stays XLA — its lookahead scratch decode is
        # not on the audited hot path.
        kernel_kw = (
            dict(decode_kernel=decode_kernel) if decode_kernel != "xla"
            else {}
        )
        if kv_layout == "paged":
            if block_size < 1:
                raise ValueError(f"block_size must be >= 1, got {block_size}")
            self.block_size = int(block_size)
            self.blocks_per_slot = -(-self.max_len // self.block_size)
            if num_blocks is None:
                # Dense-equivalent KV bytes by default (+ the trash
                # block): paging then wins by ADMITTING more, not by
                # shrinking the pool.
                num_blocks = self.num_slots * self.blocks_per_slot + 1
            self.num_blocks = int(num_blocks)
            self.allocator = BlockAllocator(self.num_blocks, self.block_size)
            self.decode_model = decode_variant(
                model, paged_blocks=self.num_blocks,
                paged_block_size=self.block_size, **quant_kw, **kernel_kw,
            )
        else:
            self.block_size = 0
            self.blocks_per_slot = 0
            self.num_blocks = 0
            self.decode_model = decode_variant(model, **quant_kw, **kernel_kw)
        # Speculative decode tier (docs/SERVING.md): spec_k draft
        # proposals per slot per tick, then ONE fixed-shape batched
        # verify runs the target over [num_slots, spec_k + 1] positions.
        # Draft sources: "int8" — greedy self-draft on the quantized
        # weights (own dense draft KV pool, quantized twin programs);
        # "ngram" — host-side prompt lookup (serving/spec.py), zero
        # device cost. Either way acceptance is data and the program
        # set stays closed (see programs_expected).
        self.spec_k = int(spec_k)
        self.spec_draft = spec_draft if self.spec_k else "off"
        self.spec_ngram_n = int(spec_ngram_n)
        # The draft decode model is ALWAYS the dense layout (its pool is
        # private lookahead scratch — block granularity buys nothing);
        # it follows the engine's kv_dtype so an int8 KV tier quantizes
        # the draft cache too.
        self._draft_model = (
            decode_variant(model, **quant_kw)
            if self.spec_draft == "int8" else None
        )
        bs = tuple(sorted(set(int(b) for b in (buckets or default_buckets(max_len)))))
        if not bs or bs[0] < 1:
            raise ValueError(f"invalid bucket ladder {bs}")
        if bs[-1] > max_len:
            raise ValueError(
                f"largest bucket {bs[-1]} exceeds max_len {max_len}"
            )
        self.buckets = bs
        if top_k_cap < 1:
            raise ValueError(f"top_k_cap must be >= 1, got {top_k_cap}")
        self.top_k_cap = int(top_k_cap)
        # Params live on device once; an already-placed (possibly
        # TP/FSDP-sharded) tree is kept as-is so GSPMD decodes in place.
        leaves = jax.tree.leaves(params)
        if leaves and all(isinstance(l, jax.Array) for l in leaves):
            self.params = params
        else:
            self.params = jax.device_put(params)
        # Inference weight quantization (SERVE_WEIGHT_DTYPE=int8|fp8): a
        # one-shot tree pass — matmul kernels + the tied embedding
        # become int8/fp8 + per-channel f32 scales; the decode programs
        # dequantize on use, so what each step STREAMS is the quantized
        # bytes (ops/quant.py).
        if weight_dtype != "bf16":
            self.params = jax.jit(
                lambda p: quantlib.quantize_params(p, dtype=weight_dtype)
            )(self.params)
        # Self-speculative draft weights: the PR-8 int8 tier of the SAME
        # model — one-shot quantized at build (any quantized
        # weight_dtype is rejected above for this source, so self.params
        # is the native tree). The draft programs dequantize on use
        # (_spec_draft_fn), so draft steps stream the int8 + scale bytes.
        self._draft_params = None
        if self.spec_draft == "int8":
            self._draft_params = jax.jit(quantlib.quantize_params)(
                self.params
            )

        # Cache pool template: shape-only trace of the decode model's
        # init at [num_slots, max_len] (no parameter initializers run).
        from distributeddeeplearning_tpu.inference import decode_cache_shapes

        tmpl = decode_cache_shapes(
            self.decode_model, self.num_slots, self.max_len
        )
        from flax import traverse_util
        from flax.core import unfreeze

        self._flatten = traverse_util.flatten_dict
        self._unflatten = traverse_util.unflatten_dict
        self._unfreeze = unfreeze
        self._template = self._flatten(unfreeze(tmpl))
        for path, leaf in self._template.items():
            if path[-1] not in _INDEX_NAMES and leaf.ndim < 2:
                raise ValueError(f"unexpected cache leaf {path}: {leaf}")
        # Draft cache template (int8 self-draft): a second dense pool at
        # the same [num_slots, max_len] geometry, written by the draft
        # programs only.
        self._draft_template = (
            self._flatten(unfreeze(decode_cache_shapes(
                self._draft_model, self.num_slots, self.max_len
            )))
            if self._draft_model is not None else None
        )

        # Host-side slot state (the scheduler-visible mirror of the
        # device pool; positions are re-fed every step, so the device
        # copies are never authoritative).
        s = self.num_slots
        self._active = np.zeros(s, bool)
        self._tokens = np.zeros(s, np.int32)
        self._positions = np.zeros(s, np.int32)
        self._temps = np.zeros(s, np.float32)
        self._top_ks = np.zeros(s, np.int32)
        self._top_ps = np.zeros(s, np.float32)
        self._eos = np.full(s, -1, np.int32)
        self._ladders: List[Optional[np.ndarray]] = [None] * s
        self._cursor = np.zeros(s, np.int64)
        # Speculative bookkeeping: the committed token BEFORE the next
        # input (the draft catch-up pair), the per-slot commit budget
        # (spec_step clamps multi-token commits to it), and — when a
        # drafter needs it — the slot's emitted history (prompt +
        # committed tokens).
        self._prev_tokens = np.zeros(s, np.int32)
        self._max_new = np.zeros(s, np.int32)
        self._history: List[Optional[List[int]]] = [None] * s
        self._drafter = (
            NgramDrafter(self.spec_ngram_n)
            if self.spec_draft == "ngram" else None
        )
        # Paged bookkeeping: per-slot block table (unused entries point
        # at the trash block 0) and the owned block-id lists.
        self._tables = (
            np.zeros((s, self.blocks_per_slot), np.int32)
            if kv_layout == "paged" else None
        )
        self._slot_blocks: List[List[int]] = [[] for _ in range(s)]
        # Introspection for the prefix-sharing oracle: what the most
        # recent prefill actually did (bucket, start, shared blocks).
        self.last_prefill: Optional[Dict[str, Any]] = None

        self._pool = None
        self._decode_exec = None
        self._prefill_exec: Dict[int, Any] = {}
        self._draft_pool = None
        self._spec_verify_exec = None
        self._spec_draft_exec = None
        self._spec_draft_prefill_exec: Dict[int, Any] = {}
        self.compile_count = 0
        self.compile_sec = 0.0
        self.decode_steps = 0
        # Prefill-program executions (the disagg bench's
        # prefill-once-per-fleet oracle: a directory adoption must add
        # exactly zero here across the whole fleet).
        self.prefill_execs = 0
        self._warmed = False
        # Brownout ladder hook (serving/scheduler.py): True routes
        # ticks through the plain decode program (already compiled —
        # the program set is unchanged); draft state keeps tracking the
        # committed stream so resuming speculation stays correct (the
        # int8 draft's KV falls behind and proposals degrade until the
        # slot turns over, but the verify commits target tokens either
        # way — a throughput knob, never a correctness one).
        self.spec_suspended = False
        # Running speculative tallies (serve_bench's accept-rate
        # percentiles; the serve.spec_* gauges/counters mirror them).
        self.spec_stats: Dict[str, Any] = {
            "verify_ticks": 0, "tokens_accepted": 0, "tokens_rejected": 0,
            "tokens_committed": 0, "draft_s": 0.0, "verify_s": 0.0,
            "accept_rates": [],
        }

    # -- cache plumbing ----------------------------------------------------

    def _zero_cache(self, batch: int, template=None):
        return self._unflatten({
            path: jnp.zeros(
                ((batch,) + leaf.shape[1:]) if leaf.ndim else (), leaf.dtype
            )
            for path, leaf in (template or self._template).items()
        })

    def _with_positions(self, cache, positions, tables=None):
        """Feed the per-step routing data: position vectors into every
        index leaf and (paged layout) the block table into every
        ``block_table`` leaf. The device copies of both are never
        authoritative — the host re-feeds them each call."""
        flat = self._flatten(self._unfreeze(cache))
        out = {}
        for path, leaf in flat.items():
            if path[-1] in _INDEX_NAMES:
                out[path] = positions
            elif tables is not None and path[-1] == _TABLE_NAME:
                out[path] = tables
            else:
                out[path] = leaf
        return self._unflatten(out)

    # -- traced programs ---------------------------------------------------

    def _live_params(self, params):
        """Dequant-on-use (``weight_dtype="int8"``/``"fp8"``): inside
        the traced program the quantized tree is the *streamed* operand;
        the f32 view XLA rebuilds here is a fused temporary, so per-step
        param traffic is the quantized + scale bytes."""
        if self.weight_dtype == "bf16":
            return params
        from distributeddeeplearning_tpu.ops import quant as quantlib

        return quantlib.dequantize_params(params)

    def _decode_fn(
        self, params, cache, tokens, positions, step_keys, temps, top_ks,
        top_ps, eos,
    ):
        params = self._live_params(params)
        cache = self._with_positions(cache, positions)
        logits, mutated = self.decode_model.apply(
            {"params": params, "cache": cache},
            tokens[:, None],
            train=False,
            mutable=["cache"],
        )
        nxt = sample_slots(
            logits[:, -1], step_keys, temps, top_ks, top_ps,
            top_k_cap=self.top_k_cap,
        )
        eos_hit = (nxt == eos) & (eos >= 0)
        return self._unfreeze(mutated["cache"]), nxt, eos_hit

    def _prefill_fn(
        self, params, pool, slot, tokens, prompt_len, key, temp, top_k,
        top_p, eos,
    ):
        params = self._live_params(params)
        # Fresh zero cache, scalar index 0: the prompt's forward IS the
        # lockstep decode path inference.generate runs — same K/V, same
        # logits at every prompt position.
        fresh = self._with_positions(
            self._zero_cache(1), jnp.zeros((), jnp.int32)
        )
        logits, mutated = self.decode_model.apply(
            {"params": params, "cache": fresh},
            tokens,
            train=False,
            mutable=["cache"],
        )
        last = lax.dynamic_index_in_dim(
            logits[0], prompt_len - 1, axis=0, keepdims=False
        )
        first = sample_slot(last, key, temp, top_k, top_p, self.top_k_cap)
        eos_hit = (first == eos) & (eos >= 0)
        mflat = self._flatten(self._unfreeze(mutated["cache"]))
        pflat = self._flatten(self._unfreeze(pool))
        out = {
            path: (
                lax.dynamic_update_slice(
                    leaf, mflat[path], (slot,) + (0,) * (leaf.ndim - 1)
                )
                if path[-1] not in _INDEX_NAMES
                else leaf
            )
            for path, leaf in pflat.items()
        }
        return self._unflatten(out), first, eos_hit

    def _decode_paged_fn(
        self, params, cache, tokens, positions, tables, step_keys, temps,
        top_ks, top_ps, eos,
    ):
        """Paged twin of :meth:`_decode_fn`: identical math per slot —
        only the KV residency differs (block pool + table routing)."""
        params = self._live_params(params)
        cache = self._with_positions(cache, positions, tables)
        logits, mutated = self.decode_model.apply(
            {"params": params, "cache": cache},
            tokens[:, None],
            train=False,
            mutable=["cache"],
        )
        nxt = sample_slots(
            logits[:, -1], step_keys, temps, top_ks, top_ps,
            top_k_cap=self.top_k_cap,
        )
        eos_hit = (nxt == eos) & (eos >= 0)
        return self._unfreeze(mutated["cache"]), nxt, eos_hit

    def _prefill_paged_fn(
        self, params, pool, table_row, start, tokens, last_idx, key, temp,
        top_k, top_p, eos,
    ):
        """Paged prefill: run the (suffix of the) prompt at absolute
        positions ``[start, start + bucket)`` THROUGH the pool — K/V
        writes scatter into the slot's table-mapped blocks, attention
        gathers any already-shared prefix blocks, and the first token is
        sampled at ``last_idx`` (the true last prompt position relative
        to ``start``). With ``start == 0`` this is a plain full-prompt
        prefill; with a prefix-cache hit it computes ONLY the divergent
        suffix — the shared blocks are never recomputed or rewritten
        (writes begin at the block-aligned ``start``). One program per
        bucket either way: start/table/last_idx are data, so the program
        set stays closed at ``len(buckets) + 1``."""
        params = self._live_params(params)
        cache = self._with_positions(pool, start, table_row)
        logits, mutated = self.decode_model.apply(
            {"params": params, "cache": cache},
            tokens,
            train=False,
            mutable=["cache"],
        )
        last = lax.dynamic_index_in_dim(
            logits[0], last_idx, axis=0, keepdims=False
        )
        first = sample_slot(last, key, temp, top_k, top_p, self.top_k_cap)
        eos_hit = (first == eos) & (eos >= 0)
        mflat = self._flatten(self._unfreeze(mutated["cache"]))
        pflat = self._flatten(self._unfreeze(pool))
        # Only the shared block pools were meaningfully mutated; the
        # [1]-batch table/index leaves are re-fed by the host anyway, so
        # the pool passes its own [num_slots]-shaped copies through.
        out = {
            path: (mflat[path] if path[-1] in _PAGED_POOL_NAMES else leaf)
            for path, leaf in pflat.items()
        }
        return self._unflatten(out), first, eos_hit

    # -- traced programs: speculative tier ---------------------------------

    def _spec_verify_core(self, params, cache, tokens, step_keys, temps,
                          top_ks, top_ps):
        """Shared tail of both verify layouts: one [S, K+1] forward of
        the target (multi-token decode view — per-row positions, writes
        land at [pos, pos+K], the position mask makes each query attend
        exactly its own prefix), then the rejection-sampling acceptance
        (serving/sampling.spec_verify_slots). Rejected-tail K/V writes
        land beyond the committed cursor and are overwritten by the next
        tick's writes before any query can attend them — the same
        trash-tail argument the bucketed prefill already relies on."""
        logits, mutated = self.decode_model.apply(
            {"params": params, "cache": cache},
            tokens,
            train=False,
            mutable=["cache"],
        )
        committed, accepted = spec_verify_slots(
            logits, tokens[:, 1:], step_keys, temps, top_ks, top_ps,
            top_k_cap=self.top_k_cap,
        )
        return self._unfreeze(mutated["cache"]), committed, accepted

    def _spec_verify_fn(self, params, pool, tokens, positions, step_keys,
                        temps, top_ks, top_ps):
        params = self._live_params(params)
        cache = self._with_positions(pool, positions)
        return self._spec_verify_core(
            params, cache, tokens, step_keys, temps, top_ks, top_ps
        )

    def _spec_verify_paged_fn(self, params, pool, tokens, positions,
                              tables, step_keys, temps, top_ks, top_ps):
        """Paged twin: identical math, K/V routed through the block
        tables (out-of-range lookahead writes land in the trash block;
        admission reserves ``spec_k`` extra positions so in-range ones
        stay inside the slot's own blocks — ``blocks_needed``)."""
        params = self._live_params(params)
        cache = self._with_positions(pool, positions, tables)
        return self._spec_verify_core(
            params, cache, tokens, step_keys, temps, top_ks, top_ps
        )

    def _spec_draft_fn(self, draft_params, dpool, catchup, positions):
        """The int8 self-draft phase as ONE program: a [S, 2] catch-up
        forward (re-feeds the previous committed token and the next
        input — after an all-accepted tick the draft cache is exactly
        one position behind, and the 2-wide window closes that gap;
        otherwise the first write is an idempotent re-write), whose last
        logits propose draft 1, then a lax.scan of K-1 greedy
        single-token steps. One dispatch per tick regardless of K. The
        dequantize runs ONCE per tick, hoisted outside the scan — K
        back-to-back draft forwards amortize one f32 materialization
        (decode_audit charges the draft steps at the dequantized bytes
        plus the resident int8 copy; re-dequantizing per scan step
        measured ~K× slower on the CPU tier for no byte win)."""
        from distributeddeeplearning_tpu.ops import quant as quantlib

        params = quantlib.dequantize_params(draft_params)
        cache = self._with_positions(dpool, positions)
        logits, mutated = self._draft_model.apply(
            {"params": params, "cache": cache},
            catchup,
            train=False,
            mutable=["cache"],
        )
        d1 = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        cache = self._unfreeze(mutated["cache"])
        if self.spec_k == 1:
            return cache, d1[:, None]

        def body(carry, _):
            cache, tok = carry
            # Position counters advance on-device inside the scan (the
            # cache's index leaves ride the carry); the host only feeds
            # the start positions. `params` is the hoisted once-per-tick
            # dequantized view from above.
            logits, mutated = self._draft_model.apply(
                {"params": params, "cache": cache},
                tok[:, None],
                train=False,
                mutable=["cache"],
            )
            nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            return (self._unfreeze(mutated["cache"]), nxt), nxt

        (cache, _), rest = lax.scan(
            body, (cache, d1), None, length=self.spec_k - 1
        )
        drafts = jnp.concatenate(
            [d1[:, None], jnp.moveaxis(rest, 0, 1)], axis=1
        )
        return cache, drafts

    def _spec_draft_prefill_fn(self, draft_params, dpool, slot, tokens):
        """Draft-pool prefill (int8 source): the full prompt through the
        quantized weights into the slot's draft rows — the draft's
        attention needs its OWN K/V of the prefix (int8-weight K/V
        differ from the target's). Always the full prompt, even when
        the target prefill rode a prefix-cache hit."""
        from distributeddeeplearning_tpu.ops import quant as quantlib

        params = quantlib.dequantize_params(draft_params)
        fresh = self._with_positions(
            self._zero_cache(1, self._draft_template),
            jnp.zeros((), jnp.int32),
        )
        _, mutated = self._draft_model.apply(
            {"params": params, "cache": fresh},
            tokens,
            train=False,
            mutable=["cache"],
        )
        mflat = self._flatten(self._unfreeze(mutated["cache"]))
        pflat = self._flatten(self._unfreeze(dpool))
        out = {
            path: (
                lax.dynamic_update_slice(
                    leaf, mflat[path], (slot,) + (0,) * (leaf.ndim - 1)
                )
                if path[-1] not in _INDEX_NAMES
                else leaf
            )
            for path, leaf in pflat.items()
        }
        return self._unflatten(out)

    # -- compilation -------------------------------------------------------

    @property
    def spec_enabled(self) -> bool:
        return self.spec_k > 0

    @property
    def programs_expected(self) -> int:
        """The closed program set's static size: decode + one prefill
        per bucket, plus — speculative tier — the batched verify and,
        for the int8 self-draft, the draft phase + one draft prefill
        per bucket. Enlarged but CLOSED: ``compile_count`` equals this
        for the engine's whole lifetime after :meth:`warmup`. A
        pool-typed engine (disaggregated serving) owns only its phase's
        programs: ``prefill`` → one per bucket, ``decode`` → one."""
        if self.pool_role == "prefill":
            return len(self.buckets)
        if self.pool_role == "decode":
            return 1
        n = len(self.buckets) + 1
        if self.spec_enabled:
            n += 1  # the [S, spec_k+1] verify
            if self.spec_draft == "int8":
                n += 1 + len(self.buckets)  # draft phase + draft prefills
        return n

    def _ensure_pools(self) -> None:
        """Build the KV pool(s) the program set closes over (idempotent).

        Canonical pool layout: index leaves are [num_slots] vectors (the
        decode step's per-slot positions) so every program — prefill
        passes them through, decode rewrites them — sees one stable
        signature; everything else keeps its template shape (dense K/V
        rows batched over slots; in the paged layout the block pools are
        batch-independent shared tensors and the block table is
        [num_slots, blocks_per_slot] routing data). Each leaf gets its
        OWN buffer: the pool is donated, and donating one aliased buffer
        through several leaves is an XLA error."""

        def zero_pool(template):
            return jax.device_put(self._unflatten({
                path: jnp.zeros(
                    (self.num_slots,) if path[-1] in _INDEX_NAMES
                    else leaf.shape,
                    jnp.int32 if path[-1] in _INDEX_NAMES else leaf.dtype,
                )
                for path, leaf in template.items()
            }))

        if self._pool is None:
            self._pool = zero_pool(self._template)
        if (
            self.spec_enabled
            and self.spec_draft == "int8"
            and self._draft_pool is None
        ):
            self._draft_pool = zero_pool(self._draft_template)

    def program_specs(self) -> List[ProgramSpec]:
        """The closed program set as data: one :class:`ProgramSpec` per
        member, each carrying the traced fn, donation, example args and
        the executable slot it installs into. :meth:`warmup` compiles
        exactly this list; the ddlint HLO audit lowers exactly this list
        — a program can't exist in one view and not the other."""
        self._ensure_pools()
        s, k = self.num_slots, self.spec_k
        paged = self.kv_layout == "paged"
        specs: List[ProgramSpec] = []

        def slot_attr(attr):
            return (
                lambda: getattr(self, attr),
                lambda ex: setattr(self, attr, ex),
            )

        def slot_dict(d, key):
            return (
                lambda: d.get(key),
                lambda ex: d.__setitem__(key, ex),
            )

        if paged:
            decode_args = (
                self.params, self._pool,
                np.zeros(s, np.int32), np.zeros(s, np.int32),
                np.zeros((s, self.blocks_per_slot), np.int32),
                np.zeros((s, 2), np.uint32),
                np.zeros(s, np.float32), np.zeros(s, np.int32),
                np.zeros(s, np.float32),
                np.full(s, -1, np.int32),
            )
        else:
            decode_args = (
                self.params, self._pool,
                np.zeros(s, np.int32), np.zeros(s, np.int32),
                np.zeros((s, 2), np.uint32),
                np.zeros(s, np.float32),
                np.zeros(s, np.int32), np.zeros(s, np.float32),
                np.full(s, -1, np.int32),
            )
        if self.pool_role != "prefill":
            specs.append(ProgramSpec(
                "decode",
                self._decode_paged_fn if paged else self._decode_fn,
                (1,), decode_args,
                {"what": "serve_decode", "slots": s},
                *slot_attr("_decode_exec"),
            ))
        if self.pool_role == "decode":
            return specs
        for bucket in self.buckets:
            if paged:
                prefill_args = (
                    self.params, self._pool,
                    np.zeros((1, self.blocks_per_slot), np.int32),
                    np.zeros(1, np.int32),
                    np.zeros((1, bucket), np.int32),
                    np.int32(0), np.zeros(2, np.uint32),
                    np.float32(0), np.int32(0), np.float32(0),
                    np.int32(-1),
                )
            else:
                prefill_args = (
                    self.params, self._pool,
                    np.int32(0), np.zeros((1, bucket), np.int32),
                    np.int32(1), np.zeros(2, np.uint32),
                    np.float32(0), np.int32(0), np.float32(0),
                    np.int32(-1),
                )
            specs.append(ProgramSpec(
                f"prefill_b{bucket}",
                self._prefill_paged_fn if paged else self._prefill_fn,
                (1,), prefill_args,
                {"what": f"serve_prefill_b{bucket}"},
                *slot_dict(self._prefill_exec, bucket),
            ))
        if self.spec_enabled:
            verify_args = [
                self.params, self._pool,
                np.zeros((s, k + 1), np.int32), np.zeros(s, np.int32),
            ]
            if paged:
                verify_args.append(
                    np.zeros((s, self.blocks_per_slot), np.int32)
                )
            verify_args += [
                np.zeros((s, k + 1, 2), np.uint32),
                np.zeros(s, np.float32), np.zeros(s, np.int32),
                np.zeros(s, np.float32),
            ]
            specs.append(ProgramSpec(
                "spec_verify",
                self._spec_verify_paged_fn if paged else self._spec_verify_fn,
                (1,), tuple(verify_args),
                {"what": "serve_spec_verify", "k": k},
                *slot_attr("_spec_verify_exec"),
            ))
            if self.spec_draft == "int8":
                specs.append(ProgramSpec(
                    "spec_draft",
                    self._spec_draft_fn,
                    (1,),
                    (
                        self._draft_params, self._draft_pool,
                        np.zeros((s, 2), np.int32), np.zeros(s, np.int32),
                    ),
                    {"what": "serve_spec_draft", "k": k},
                    *slot_attr("_spec_draft_exec"),
                ))
                for bucket in self.buckets:
                    specs.append(ProgramSpec(
                        f"spec_draft_prefill_b{bucket}",
                        self._spec_draft_prefill_fn,
                        (1,),
                        (
                            self._draft_params, self._draft_pool,
                            np.int32(0), np.zeros((1, bucket), np.int32),
                        ),
                        {"what": f"serve_spec_draft_prefill_b{bucket}"},
                        *slot_dict(self._spec_draft_prefill_exec, bucket),
                    ))
        return specs

    def warmup(self) -> Dict[str, float]:
        """AOT-compile the decode step and every bucket's prefill
        (idempotent) — plus, with speculation on, the verify and draft
        programs. After this the engine's program set is closed:
        ``compile_count == programs_expected`` for its whole lifetime."""
        log = get_logger()
        t_all = time.perf_counter()

        def compile_one(ps: ProgramSpec) -> Tuple[Any, float]:
            with obs.span("compile", **ps.span):
                t0 = time.perf_counter()
                compiled = (
                    jax.jit(ps.fn, donate_argnums=ps.donate_argnums)
                    .lower(*ps.example_args)
                    .compile()
                )
                return compiled, time.perf_counter() - t0

        # The members are independent and XLA compiles outside the GIL,
        # so the set compiles side by side: on the TPU every member
        # spends ~30 s on the sampler's full-vocab sort alone, and nine
        # of them in a row made a cold start take minutes.
        pending = [ps for ps in self.program_specs() if not ps.installed]
        if pending:
            workers = min(len(pending), os.cpu_count() or 1)
            with concurrent.futures.ThreadPoolExecutor(workers) as pool:
                for ps, (compiled, secs) in zip(
                    pending, pool.map(compile_one, pending)
                ):
                    ps.install(compiled)
                    # scope table (obs/programs.py): names on this
                    # program's device time; parsed only when asked
                    obs_programs.register(
                        "jit_" + getattr(ps.fn, "__name__", ps.name),
                        compiled, owner=self, key=ps.name,
                    )
                    self.compile_sec += secs
                    self.compile_count += 1
        self._warmed = True
        if self.kv_layout == "paged":
            self._emit_pool_gauges()
        acct = self.byte_accounting()
        obs.gauge(
            "serve.kv_bytes_per_token", float(acct["kv_bytes_per_token"])
        )
        obs.gauge("serve.param_bytes", float(acct["param_bytes"]))
        # Which decode lowering this engine compiled (0 = xla stitched,
        # 1 = fused Pallas kernel); the string rides as a label.
        obs.gauge(
            "serve.decode_kernel",
            1.0 if self.decode_kernel == "fused" else 0.0,
            kernel=self.decode_kernel,
        )
        info = {
            # summed over the programs; they compile side by side, so
            # the wall time of a cold warmup is the smaller wall_sec
            "compile_sec": self.compile_sec,
            "wall_sec": time.perf_counter() - t_all,
            "programs": float(self.compile_count),
        }
        log.info(
            "serve warmup: %d programs (decode + %d prefill buckets %s%s) "
            "in %.2fs, slots=%d cache_len=%d",
            self.compile_count, len(self.buckets), list(self.buckets),
            (f" + spec k={self.spec_k} draft={self.spec_draft}"
             if self.spec_enabled else ""),
            time.perf_counter() - t_all, self.num_slots, self.max_len,
        )
        obs.gauge("serve.programs", float(self.compile_count))
        return info

    # -- slot lifecycle ----------------------------------------------------

    def _emit_pool_gauges(self) -> None:
        a = self.allocator
        obs.gauge("serve.block_pool_total", float(a.capacity))
        obs.gauge("serve.block_pool_free", float(a.free_count))
        obs.gauge("serve.prefix_hits", float(a.stats["prefix_hit_blocks"]))

    def pool_stats(self) -> Optional[Dict[str, int]]:
        """Block-pool gauges (None on the dense layout)."""
        return None if self.allocator is None else self.allocator.snapshot()

    def byte_accounting(self) -> Dict[str, float]:
        """Dtype-aware byte ledger (the ``serve.kv_bytes_per_token`` /
        ``serve.param_bytes`` gauges, serve_bench's quant compare):
        KV-pool bytes per cached token position — int8 payload PLUS f32
        scales when ``kv_dtype="int8"``, never just the payload — and
        the resident param bytes a decode step streams (a quantized
        tree counts its int8 + scale leaves)."""
        kv = 0
        for path, leaf in self._template.items():
            if path[-1] in _INDEX_NAMES or path[-1] == _TABLE_NAME:
                continue
            kv += (
                int(np.prod(leaf.shape, dtype=np.int64))
                * np.dtype(leaf.dtype).itemsize
            )
        positions = (
            self.num_blocks * self.block_size if self.kv_layout == "paged"
            else self.num_slots * self.max_len
        )
        param_bytes = sum(
            leaf.size * np.dtype(leaf.dtype).itemsize
            for leaf in jax.tree.leaves(self.params)
        )
        out = {
            "kv_pool_bytes": float(kv),
            "kv_bytes_per_token": kv / max(positions, 1),
            "param_bytes": float(param_bytes),
        }
        # Speculative tier (int8 self-draft): the draft's resident bytes
        # are itemized, never hidden — a second dense KV pool plus the
        # quantized weight tree (decode_audit --spec-k charges both).
        if self.spec_draft == "int8":
            dkv = sum(
                int(np.prod(leaf.shape, dtype=np.int64))
                * np.dtype(leaf.dtype).itemsize
                for path, leaf in self._draft_template.items()
                if path[-1] not in _INDEX_NAMES
            )
            out["draft_kv_pool_bytes"] = float(dkv)
            out["draft_param_bytes"] = float(sum(
                leaf.size * np.dtype(leaf.dtype).itemsize
                for leaf in jax.tree.leaves(self._draft_params)
            ))
        return out

    def blocks_needed(self, prompt_len: int, max_new_tokens: int) -> int:
        """Physical blocks a request writes: positions 0 ..
        prompt_len + max_new_tokens - 2 (the final sampled token is
        never fed back, so its K/V is never written). The speculative
        tier reserves ``spec_k`` positions MORE: a verify writes K
        lookahead candidates past the committed cursor, and reserving
        them keeps those transient writes inside the slot's own blocks
        instead of thrashing the trash block."""
        return self.allocator.blocks_for_tokens(
            prompt_len + max_new_tokens - 1 + self.spec_k
        )

    def can_admit(self, spec: "ReqSpec") -> bool:
        """Admission gate beyond slot availability: on the paged layout
        a request needs its (prefix-discounted) block count free. The
        scheduler checks this before committing a queue pop — block
        exhaustion is backpressure, not an error."""
        if self.allocator is None:
            return True
        prompt = np.asarray(spec.prompt, np.int32).reshape(-1)
        t = prompt.shape[0]
        hit = (
            self.allocator.peek_prefix(prompt, t - 1)
            if self.prefix_cache else 0
        )
        hit = self._prefix_fit(t, hit)
        need = self.blocks_needed(t, spec.max_new_tokens) - hit
        return self.allocator.free_count >= max(need, 0)

    def _prefix_fit(self, t: int, n_blocks: int) -> int:
        """Largest usable cached-prefix block count for a ``t``-token
        prompt. A prefix hit shifts the suffix program's bucket window
        to ``[start, start + bucket)``; rows past ``max_len`` have no
        position embedding — the padded tail gathers NaN fill, the NaN
        K/V lands in the trash block, and the zero-masked-weight ×
        NaN value product poisons every slot's attention output.
        Recomputing a few cached positions is correct; a NaN is never
        recoverable."""
        start = n_blocks * self.block_size
        while n_blocks and start + self.bucket_for(t - start) > self.max_len:
            n_blocks -= 1
            start -= self.block_size
        return n_blocks

    @property
    def free_slots(self) -> List[int]:
        return [i for i in range(self.num_slots) if not self._active[i]]

    @property
    def active_slots(self) -> List[int]:
        return [i for i in range(self.num_slots) if self._active[i]]

    @property
    def occupancy(self) -> float:
        return float(self._active.sum()) / self.num_slots

    def bucket_for(self, prompt_len: int) -> int:
        for b in self.buckets:
            if prompt_len <= b:
                return b
        raise ValueError(
            f"prompt length {prompt_len} exceeds the largest bucket "
            f"{self.buckets[-1]}"
        )

    def validate_spec(self, spec: ReqSpec) -> int:
        """Full admission validation (shape limits + the sort-free
        sampling cap) — called by ``Server.submit`` so a malformed
        request fails the *submitting* caller, never the serving loop.
        Returns the effective top_k (``top_k >= vocab`` maps to 0 =
        filter off, the reference's clamp — same draw)."""
        spec.validate(self.max_len, self.buckets[-1])
        if self.spec_enabled:
            t = int(np.asarray(spec.prompt).shape[-1])
            if t + spec.max_new_tokens + self.spec_k > self.max_len:
                # dynamic_update_slice clamps out-of-range starts, so a
                # verify window spilling past max_len would CORRUPT
                # earlier rows — the dense analogue of the paged
                # lookahead reservation.
                raise ValueError(
                    f"prompt {t} + max_new_tokens {spec.max_new_tokens} "
                    f"+ spec_k {self.spec_k} lookahead exceeds the "
                    f"engine cache length {self.max_len}; shorten the "
                    "request or build the engine with max_len + spec_k"
                )
        if self.allocator is not None:
            t = int(np.asarray(spec.prompt).shape[-1])
            worst = self.blocks_needed(t, spec.max_new_tokens)
            if worst > self.allocator.capacity:
                raise ValueError(
                    f"request needs {worst} KV blocks but the pool holds "
                    f"{self.allocator.capacity}; raise SERVE_NUM_BLOCKS / "
                    "SlotEngine(num_blocks=...)"
                )
        tk = int(spec.top_k or 0)
        vocab = getattr(self.model, "vocab_size", None)
        if tk and vocab is not None and tk >= int(vocab):
            tk = 0
        if tk > self.top_k_cap and spec.top_p is None:
            # Without nucleus sampling the request runs the sort-free
            # path, whose static lax.top_k window is the cap.
            raise ValueError(
                f"top_k {tk} exceeds the engine's sort-free cap "
                f"{self.top_k_cap}; raise SlotEngine(top_k_cap=...) / "
                "SERVE_TOP_K_CAP"
            )
        return tk

    def prefill(self, slot: int, spec: ReqSpec) -> Tuple[int, bool]:
        """Admit ``spec`` into ``slot``: run the bucketed prefill, seat
        the request's sampling state, and return (first token, eos hit).
        The slot is occupied afterwards even on an immediate eos — the
        caller decides to :meth:`release`."""
        if self._active[slot]:
            raise ValueError(f"slot {slot} is occupied")
        if self.pool_role == "decode":
            raise RuntimeError(
                "a decode-pool engine has no prefill programs; requests "
                "reach it only through import_slot (handoff/migration)"
            )
        tk = self.validate_spec(spec)
        if not self._warmed:
            self.warmup()
        prompt = np.asarray(spec.prompt, np.int32).reshape(-1)
        t = prompt.shape[0]
        sampled = spec.temperature > 0.0
        # Speculative ticks consume one key per VERIFY POSITION (cursor
        # .. cursor+K), so the ladder carries spec_k lookahead rows past
        # max_new_tokens. The partitionable-threefry split is
        # prefix-stable in n (serving/keys.py), so rows 0..max_new-1
        # are unchanged — spec off/on cannot re-key the non-spec path.
        ladder = (
            keylib.request_key_ladder(
                spec.key_data(), spec.max_new_tokens + self.spec_k
            )
            if sampled
            else None
        )
        key0 = ladder[0] if sampled else np.zeros(2, np.uint32)
        temp = np.float32(spec.temperature if sampled else 0.0)
        top_k = np.int32(tk)
        top_p = np.float32(spec.top_p or 0.0)
        eos = np.int32(-1 if spec.eos_token is None else spec.eos_token)
        if self.allocator is not None:
            first, eos_hit = self._prefill_paged(
                slot, spec, prompt, key0, temp, top_k, top_p, eos
            )
        else:
            bucket = self.bucket_for(t)
            padded = np.zeros((1, bucket), np.int32)
            padded[0, :t] = prompt
            self._pool, first, eos_hit = self._prefill_exec[bucket](
                self.params, self._pool, np.int32(slot), padded,
                np.int32(t), np.asarray(key0, np.uint32), temp, top_k,
                top_p, eos,
            )
            self.prefill_execs += 1
            self.last_prefill = {
                "slot": slot, "bucket": bucket, "start": 0,
                "shared_blocks": 0,
            }
        self._active[slot] = True
        self._tokens[slot] = int(first)
        self._positions[slot] = t
        self._temps[slot] = temp
        self._top_ks[slot] = top_k
        self._top_ps[slot] = top_p
        self._eos[slot] = eos
        self._ladders[slot] = ladder
        self._cursor[slot] = 1
        if self.spec_enabled:
            self._max_new[slot] = spec.max_new_tokens
            self._prev_tokens[slot] = int(prompt[-1])
            self._history[slot] = [int(x) for x in prompt] + [int(first)]
            if self.spec_draft == "int8":
                bucket = self.bucket_for(t)
                padded = np.zeros((1, bucket), np.int32)
                padded[0, :t] = prompt
                self._draft_pool = self._spec_draft_prefill_exec[bucket](
                    self._draft_params, self._draft_pool, np.int32(slot),
                    padded,
                )
        return int(first), bool(eos_hit)

    def _prefill_paged(
        self, slot, spec, prompt, key0, temp, top_k, top_p, eos
    ) -> Tuple[Any, Any]:
        """Paged admission: match the prompt's block-aligned prefix
        against the prefix cache, allocate the remaining blocks
        (all-or-nothing; :class:`BlockPoolExhausted` propagates as
        backpressure), and prefill ONLY the divergent suffix through the
        slot's block table. The match is capped at ``prompt_len - 1``
        tokens so at least the last prompt position is always computed —
        the first token's logits come from this program."""
        a = self.allocator
        t = prompt.shape[0]
        shared: List[int] = (
            a.match_prefix(prompt, t - 1) if self.prefix_cache else []
        )
        keep = self._prefix_fit(t, len(shared))
        if keep < len(shared):
            a.release_match(shared[keep:])
            shared = shared[:keep]
        start = len(shared) * self.block_size
        suffix = prompt[start:]
        suffix_len = t - start
        bucket = self.bucket_for(suffix_len)
        need_new = self.blocks_needed(t, spec.max_new_tokens) - len(shared)
        try:
            fresh = a.alloc(max(need_new, 0))
        except BlockPoolExhausted:
            a.release_match(shared)
            raise
        blocks = shared + fresh
        table_row = np.zeros((1, self.blocks_per_slot), np.int32)
        table_row[0, :len(blocks)] = blocks
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :suffix_len] = suffix
        self._pool, first, eos_hit = self._prefill_exec[bucket](
            self.params, self._pool, table_row,
            np.asarray([start], np.int32), padded,
            np.int32(suffix_len - 1), np.asarray(key0, np.uint32), temp,
            top_k, top_p, eos,
        )
        self.prefill_execs += 1
        if self.prefix_cache:
            # The full prompt blocks this request owns are now written
            # and immutable (decode writes start at prompt_len) — make
            # them discoverable. Already-shared blocks are skipped.
            a.register_prefix(prompt, blocks)
        self._tables[slot] = table_row[0]
        self._slot_blocks[slot] = blocks
        self.last_prefill = {
            "slot": slot, "bucket": bucket, "start": start,
            "shared_blocks": len(shared), "blocks": list(blocks),
        }
        if len(shared):
            obs.counter("serve.prefix_hit_blocks", len(shared))
        self._emit_pool_gauges()
        return first, eos_hit

    def decode_step(self) -> List[Tuple[int, int, bool]]:
        """One batched decode tick: every occupied slot emits its next
        token. Returns ``[(slot, token, eos_hit), ...]`` for occupied
        slots (empty when the pool is idle)."""
        slots = self.active_slots
        if not slots:
            return []
        step_keys = np.zeros((self.num_slots, 2), np.uint32)
        for i in slots:
            ladder = self._ladders[i]
            if ladder is not None:
                step_keys[i] = ladder[min(self._cursor[i], len(ladder) - 1)]
        if self.allocator is not None:
            self._pool, nxt, eos_hit = self._decode_exec(
                self.params, self._pool, self._tokens, self._positions,
                self._tables, step_keys, self._temps, self._top_ks,
                self._top_ps, self._eos,
            )
        else:
            self._pool, nxt, eos_hit = self._decode_exec(
                self.params, self._pool, self._tokens, self._positions,
                step_keys, self._temps, self._top_ks, self._top_ps,
                self._eos,
            )
        nxt = np.array(nxt)
        eos_hit = np.array(eos_hit)
        self.decode_steps += 1
        out = []
        for i in slots:
            if self.spec_k:
                # A spec engine stepping plainly (brownout spec_off):
                # keep the drafter's view of the committed stream
                # current so resuming speculation proposes from real
                # history.
                self._prev_tokens[i] = int(self._tokens[i])
                if self._history[i] is not None:
                    self._history[i].append(int(nxt[i]))
            self._tokens[i] = nxt[i]
            self._positions[i] += 1
            self._cursor[i] += 1
            out.append((i, int(nxt[i]), bool(eos_hit[i])))
        return out

    def spec_step(self) -> List[Tuple[int, List[int], bool]]:
        """One speculative tick: draft ``spec_k`` proposals per slot,
        ONE batched verify of the target over ``[num_slots, spec_k+1]``
        positions, commit per-slot ``1 .. spec_k+1`` tokens. Returns
        ``[(slot, committed_tokens, eos_hit), ...]`` for occupied slots
        — each list already clamped to the request's remaining token
        budget and truncated at eos (the scheduler releases on either).
        """
        if not self.spec_enabled:
            raise RuntimeError("spec_step requires SlotEngine(spec_k > 0)")
        slots = self.active_slots
        if not slots:
            return []
        s, k = self.num_slots, self.spec_k
        tokens = np.zeros((s, k + 1), np.int32)
        tokens[:, 0] = self._tokens
        t0 = time.perf_counter()
        if self.spec_draft == "int8":
            catchup = np.stack(
                [self._prev_tokens, self._tokens], axis=1
            ).astype(np.int32)
            self._draft_pool, drafts = self._spec_draft_exec(
                self._draft_params, self._draft_pool, catchup,
                np.maximum(self._positions - 1, 0).astype(np.int32),
            )
            drafts = np.asarray(drafts)
        else:
            drafts = propose_all(self._drafter, self._history, slots, s, k)
        draft_s = time.perf_counter() - t0
        tokens[:, 1:] = drafts
        step_keys = np.zeros((s, k + 1, 2), np.uint32)
        for i in slots:
            ladder = self._ladders[i]
            if ladder is not None:
                c = int(self._cursor[i])
                step_keys[i] = ladder[c:c + k + 1]
        t1 = time.perf_counter()
        if self.allocator is not None:
            self._pool, committed, accepted = self._spec_verify_exec(
                self.params, self._pool, tokens, self._positions,
                self._tables, step_keys, self._temps, self._top_ks,
                self._top_ps,
            )
        else:
            self._pool, committed, accepted = self._spec_verify_exec(
                self.params, self._pool, tokens, self._positions,
                step_keys, self._temps, self._top_ks, self._top_ps,
            )
        committed = np.asarray(committed)
        accepted = np.asarray(accepted)
        verify_s = time.perf_counter() - t1
        self.decode_steps += 1
        out: List[Tuple[int, List[int], bool]] = []
        acc_total = rej_total = commit_total = 0
        for i in slots:
            a = int(accepted[i])
            acc_total += a
            rej_total += k - a
            remaining = int(self._max_new[i]) - int(self._cursor[i])
            n = min(a + 1, remaining)
            toks = [int(x) for x in committed[i, :n]]
            eos = int(self._eos[i])
            eos_hit = False
            if eos >= 0:
                for j, tok in enumerate(toks):
                    if tok == eos:
                        toks = toks[: j + 1]
                        eos_hit = True
                        break
            n = len(toks)
            commit_total += n
            self._prev_tokens[i] = (
                toks[-2] if n >= 2 else int(self._tokens[i])
            )
            self._tokens[i] = toks[-1]
            self._positions[i] += n
            self._cursor[i] += n
            if self._history[i] is not None:
                self._history[i].extend(toks)
            out.append((i, toks, eos_hit))
        st = self.spec_stats
        st["verify_ticks"] += 1
        st["tokens_accepted"] += acc_total
        st["tokens_rejected"] += rej_total
        st["tokens_committed"] += commit_total
        st["draft_s"] += draft_s
        st["verify_s"] += verify_s
        rate = acc_total / max(len(slots) * k, 1)
        if len(st["accept_rates"]) < 100_000:
            st["accept_rates"].append(rate)
        obs.gauge("serve.spec_accept_rate", rate)
        obs.gauge("serve.spec_draft_ms", draft_s * 1e3)
        obs.gauge("serve.spec_verify_ms", verify_s * 1e3)
        obs.counter("serve.spec_tokens_accepted", acc_total)
        obs.counter("serve.spec_tokens_rejected", rej_total)
        return out

    def force_token(self, slot: int, token: int) -> None:
        """Teacher-forcing hook for quality oracles (serve_bench's
        quantization compare, ``tests/test_serving_quant.py``): override
        the token the NEXT decode step feeds this slot. The step then
        answers "given this exact context, what would the engine emit?"
        — per-step agreement without free-running divergence cascades.
        Positions/keys/sampling state are untouched; never use while a
        request's own stream matters."""
        if not self._active[slot]:
            raise ValueError(f"slot {slot} is not occupied")
        self._tokens[slot] = np.int32(token)

    def release(self, slot: int) -> None:
        """Free a slot (eviction). Pure host bookkeeping — the stale
        cache rows are unreachable (per-slot position masks) and fully
        overwritten by the next prefill into this slot. On the paged
        layout the slot's blocks are dereferenced (prefix-cached blocks
        stay resident and evictable; private ones return to the free
        list) and its table row re-points at the trash block."""
        self._active[slot] = False
        self._ladders[slot] = None
        self._tokens[slot] = 0
        self._positions[slot] = 0
        self._temps[slot] = 0.0
        self._top_ks[slot] = 0
        self._top_ps[slot] = 0.0
        self._eos[slot] = -1
        self._cursor[slot] = 0
        self._prev_tokens[slot] = 0
        self._max_new[slot] = 0
        self._history[slot] = None
        if self.allocator is not None:
            for bid in self._slot_blocks[slot]:
                self.allocator.decref(bid)
            self._slot_blocks[slot] = []
            self._tables[slot] = 0
            self._emit_pool_gauges()

    # -- slot state transfer (disaggregation / migration) ------------------

    def export_blocks(self, block_ids) -> Dict[Tuple[str, ...], np.ndarray]:
        """Host-stage the KV content of ``block_ids``: leaf path ->
        ``[len(block_ids), block_size, ...]`` numpy rows gathered from
        every paged pool leaf. Pure read — no program runs, the pool is
        untouched. The caller must hold the blocks resident (referenced
        or pinned) for the read to be meaningful."""
        if self.allocator is None:
            raise RuntimeError("export_blocks requires kv_layout='paged'")
        idx = np.asarray(list(block_ids), np.int64)
        flat = self._flatten(self._unfreeze(self._pool))
        out: Dict[Tuple[str, ...], np.ndarray] = {}
        for path, leaf in flat.items():
            if path[-1] in _PAGED_POOL_NAMES:
                out[path] = np.asarray(leaf)[idx].copy()
        return out

    def _import_block_payload(self, block_ids, payload) -> None:
        """Write host-staged block content into ``block_ids`` of the
        local pool. Host copy + ``jax.device_put`` — no program runs,
        nothing compiles, so the closed program set is untouched (the
        CPU tier's stand-in for a device-to-device block DMA)."""
        idx = np.asarray(list(block_ids), np.int64)
        flat = self._flatten(self._unfreeze(self._pool))
        out = {}
        for path, leaf in flat.items():
            if path[-1] in _PAGED_POOL_NAMES and path in payload:
                host = np.array(leaf)
                host[idx] = payload[path]
                out[path] = jax.device_put(host)
            else:
                out[path] = leaf
        self._pool = self._unflatten(out)

    def export_slot(self, slot: int) -> Dict[str, Any]:
        """Snapshot everything slot ``slot`` needs to continue decoding
        bitwise-identically on ANOTHER engine: the sampling state, the
        key-ladder cursor, and the host-staged content of every written
        KV block. The slot itself is untouched — the caller releases it
        (handoff) or keeps it (directory publish reads). The importing
        engine replays nothing: decode resumes at the exact cursor with
        the exact ladder row, so the continuation is the same stream the
        exporting engine would have produced."""
        if self.allocator is None:
            raise RuntimeError("export_slot requires kv_layout='paged'")
        if self.spec_enabled:
            raise RuntimeError(
                "export_slot is incompatible with spec_k > 0 (the draft "
                "pool's lookahead state does not travel)"
            )
        if not self._active[slot]:
            raise ValueError(f"slot {slot} is not occupied")
        written = int(self._positions[slot])
        blocks = list(self._slot_blocks[slot])
        nwritten = self.allocator.blocks_for_tokens(written)
        ladder = self._ladders[slot]
        return {
            "block_size": self.block_size,
            "n_blocks": len(blocks),
            "blocks": blocks,
            "written": written,
            "token": int(self._tokens[slot]),
            "temp": float(self._temps[slot]),
            "top_k": int(self._top_ks[slot]),
            "top_p": float(self._top_ps[slot]),
            "eos": int(self._eos[slot]),
            "ladder": None if ladder is None else np.array(ladder),
            "cursor": int(self._cursor[slot]),
            "payload": self.export_blocks(blocks[:nwritten]),
        }

    def can_import(self, state: Dict[str, Any]) -> bool:
        """Room for an imported slot right now? (a free slot AND the
        state's block count allocatable)."""
        if self.allocator is None:
            return False
        return (
            bool(self.free_slots)
            and self.allocator.free_count >= int(state["n_blocks"])
        )

    def import_slot(
        self, slot: int, state: Dict[str, Any],
        prompt: Optional[np.ndarray] = None,
    ) -> None:
        """Seat an exported slot state (:meth:`export_slot`, or a
        directory adoption's synthetic state): allocate fresh blocks,
        write the staged KV content, and restore the sampling state so
        the next :meth:`decode_step` continues the stream bitwise.
        ``prompt`` (when given, with the prefix cache on) registers the
        full prompt blocks locally so later requests prefix-hit here."""
        if self.allocator is None:
            raise RuntimeError("import_slot requires kv_layout='paged'")
        if self.spec_enabled:
            raise RuntimeError("import_slot is incompatible with spec_k > 0")
        if self._active[slot]:
            raise ValueError(f"slot {slot} is occupied")
        if int(state["block_size"]) != self.block_size:
            raise ValueError(
                f"block_size mismatch: exported {state['block_size']}, "
                f"local {self.block_size}"
            )
        if not self._warmed:
            self.warmup()
        n = int(state["n_blocks"])
        blocks = self.allocator.alloc(n)  # BlockPoolExhausted -> caller
        nwritten = self.allocator.blocks_for_tokens(int(state["written"]))
        self._import_block_payload(blocks[:nwritten], state["payload"])
        self._tables[slot] = 0
        self._tables[slot, :n] = blocks
        self._slot_blocks[slot] = blocks
        self._active[slot] = True
        self._tokens[slot] = np.int32(state["token"])
        self._positions[slot] = np.int32(state["written"])
        self._temps[slot] = np.float32(state["temp"])
        self._top_ks[slot] = np.int32(state["top_k"])
        self._top_ps[slot] = np.float32(state["top_p"])
        self._eos[slot] = np.int32(state["eos"])
        ladder = state.get("ladder")
        self._ladders[slot] = None if ladder is None else np.array(ladder)
        self._cursor[slot] = int(state["cursor"])
        if prompt is not None and self.prefix_cache:
            self.allocator.register_prefix(
                np.asarray(prompt, np.int32).reshape(-1), blocks
            )
        self._emit_pool_gauges()

    def adopt_prefix_blocks(self, tokens, payload) -> int:
        """Seed the LOCAL prefix cache with directory-fetched full-block
        content (a chain prefetch): allocate, write, register, then
        decref into the evictable cache. The next prefill of a prompt
        starting with ``tokens``' leading blocks hits locally and
        computes only its suffix. Returns the number of blocks seeded
        (0 when already cached or no room — prefill then computes them,
        which is always correct, just not free)."""
        if self.allocator is None or not self.prefix_cache:
            return 0
        a = self.allocator
        toks = np.asarray(tokens, np.int32).reshape(-1)
        n = min(
            (len(next(iter(payload.values()))) if payload else 0),
            len(toks) // self.block_size,
        )
        if n < 1:
            return 0
        if a.peek_prefix(toks, n * self.block_size) >= n:
            return 0
        if a.free_count < n:
            return 0
        blocks = a.alloc(n)
        self._import_block_payload(
            blocks, {p: arr[:n] for p, arr in payload.items()}
        )
        a.register_prefix(toks[: n * self.block_size], blocks)
        for bid in blocks:
            a.decref(bid)
        self._emit_pool_gauges()
        return n
