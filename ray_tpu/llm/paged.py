"""Paged-KV continuous-batching engine with prefix page sharing
(reference: vLLM's PagedAttention as delegated by
python/ray/llm/_internal/serve/deployments/llm/vllm/vllm_models.py, and
the prefix-aware machinery in serve/request_router/; re-designed
TPU-native: page pools in the Pallas paged-attention kernel's layout,
one jitted decode step for the whole active batch).

HBM scales with tokens-in-flight (`num_pages x page_size`), not
`max_batch x max_len`; full prompt pages shared byte-identically across
requests via a prefix hash (system prompts stored once); admission
blocks on page budget, not on a row's shape.

Scheduling is continuous (iteration-level): every tick fills
freed slots from the waiting queue, advances at most
`prefill_decode_ratio` chunked-prefill chunks interleaved with the
decode batch, and under page pressure preempts the youngest sequence
(pages released, request parked for re-admission with its generated
tokens as a prompt extension) instead of exhausting the pool. Prefix
reuse rides a radix tree over KV pages (`radix.py`): admission maps the
longest cached prefix copy-on-write into the block table and prefills
only the tail.

The tick runs one decode step ahead of the host: a visit dispatches step
n+1 before it reads step n's tokens, which stay on the device and feed
step n+1 there, so the host's part of a tick runs while the chip
computes (`PagedLLMEngine.step`).
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import os
import queue
import re
import time
from typing import (TYPE_CHECKING, Any, Callable, Dict, List, Optional,
                    Tuple, Union)

import jax
import jax.numpy as jnp
import numpy as np

from .._internal import accel as _accel
from .._internal.config import CONFIG
from ..models.llama import LlamaConfig, LlamaModel, init_kv_caches
from ..models.moe import sorted_buckets
from ..ops.latent_attention import (latent_kernel, pages_spared,
                                    share_schedule)
from ..ops.paged_attention import paged_kernel
from ..ops.sparse_attention import sparse_kernel
from . import reqtrace
from ._metrics import llm_metrics
from .radix import RadixPrefixCache
from .sampling import (SAMPLER_TIERS, sample_tokens, sample_with_confidence,
                       unmask_block, unmask_count)
from .staging import StagedRows

if TYPE_CHECKING:
    from ..models.evabyte import EvaByteConfig
    from ..models.falcon_h1 import FalconH1Config
    from ..models.keye_dsa import KeyeDSAConfig
    from ..models.lfm2 import Lfm2Config
    from ..models.nemotron_h import NemotronHConfig
    from ..models.sarvam_mla import SarvamMLAConfig
    from ..models.sdar import SdarConfig

_TAGS = {"engine": "paged"}
# gauges are per-process series (see _metrics.py on the merge semantics)
_GAUGE_TAGS = {"engine": "paged", "pid": str(os.getpid())}


@dataclasses.dataclass
class GenerationRequest:
    prompt_tokens: List[int]
    max_new_tokens: int = 32
    request_id: str = ""
    temperature: Optional[float] = None
    # 0/None = no k filter; 1.0/None = no nucleus filter (vLLM-style
    # SamplingParams; applied inside the jitted decode, sampling.py)
    top_k: Optional[int] = None
    top_p: Optional[float] = None
    # request-observatory labels: propagated by the serve proxy
    # (X-RTPU-Tenant, matched route prefix) down to the engine and
    # folded into per-tenant/per-route percentiles (llm/reqtrace.py)
    tenant: Optional[str] = None
    route: Optional[str] = None
    # for a model that generates by diffusion over blocks (`_blockwise`;
    # None: the model configuration's own): denoising forwards a block
    # (1 .. block_length: quality against latency), the unmasking rule
    # ("static" or "dynamic": both rank by low confidence) and the dynamic
    # rule's threshold; every other model ignores them
    denoising_steps: Optional[int] = None
    remasking: Optional[str] = None
    confidence_threshold: Optional[float] = None


@dataclasses.dataclass
class PagedEngineConfig:
    # What the engine asks of a model's configuration: its layer count, kv
    # heads and head size, its type, and its flax module (`module()`; a
    # LlamaConfig's is LlamaModel). One whose rows carry recurrent state
    # beside their pages says in what shape and type (`state_shapes()`)
    # and makes it (`init_state(rows)`): a scanning layer keeps the arrays
    # `state_shapes()` names, in that order, each a pool of `max_batch`
    # rows: a convolution window and a scan state (`conv`, `ssm`), or a
    # window alone. One whose layers are not all of
    # one kind says what each keeps (`layer_caches()`), makes state for
    # the layers that scan only, and may carry per-layer accumulators
    # through the decode step (`init_counters()`); `_module_of` /
    # `_recurrent` / `_layer_caches` below. One whose rows do not keep the
    # K/V of their whole context says what they keep instead (`_windowed`):
    # the rows of its pages a row of n positions holds and its next token
    # attends (`cache_rows(n)`, which its own decode path applies to
    # `lengths`), the pages that makes (`pages_held(n, page_size)`), the
    # most a row holds on its way to n (`prefill_pages`: the admission
    # budget, and at the longest row the block table's width), whether a
    # position closes a window (`window_closes(n)`: the engine then runs
    # `compress_window_pages` on the row's open window and takes back the
    # pages it emptied), and which page sizes and buckets it can live
    # with (`check_pages`). Its prefill chunk reads and writes the row's
    # pages directly; nothing of a row is staged densely. One whose layers
    # cache ONE latent row a token, key and value at once, says how wide
    # (`latent_cache()`: lanes of a row in the pool, lanes of it that are
    # the value; `_latent`): a layer then keeps one pool `[1, pages,
    # page_size, lanes]` and no V pool, its prefill chunks go straight into
    # the row's pages through its table as a `_windowed` model's do, and a
    # radix-shared prefix is mapped in place and never copied. One whose
    # layers SELECT what they attend by an indexer's scores says how wide
    # an index key stands in its pool (`index_cache()`: lanes; `_indexed`)
    # and how many tokens a query selects (`index_topk`): a layer then
    # keeps an index-key pool `[1, pages, page_size, lanes]` beside its K
    # and V pools, which stand token-major (`[1, pages, page_size, kv_heads
    # * head_dim]`: a selected token's K is one row), all three addressed by
    # the same page ids and block table; its prefill chunks write all three
    # straight into the row's pages, and a radix-shared prefix maps K, V and
    # index pages in place (a radix node stays a page id; nothing is
    # copied). One that lays its own K and V pools says in what shape
    # (`page_pool(pages, page_size)`; `_pooled`: heads narrower than a lane
    # tile stand side by side in a row, `ops.paged_attention`): its prefill
    # chunks then write and attend the row's pages through its table, and
    # of a prefilling row only the scanning layers' state is staged. One
    # that GENERATES BY DIFFUSION OVER BLOCKS says how long a block is and
    # which id is the mask (`block_length`, `mask_token_id`; `_blockwise`):
    # a row's step is then one forward of the `block_length` positions of
    # its open block, attended both ways, and yields 0 .. block_length
    # tokens; a block whose last mask is gone is committed by one more
    # forward, and only then does the row's cached length move
    # (`PagedLLMEngine._block_tick`); the prompt's whole blocks are
    # prefilled in place under the same mask and its last `len %
    # block_length` tokens open the first block.
    model: Union[LlamaConfig, "FalconH1Config", "NemotronHConfig",
                 "EvaByteConfig", "SarvamMLAConfig", "KeyeDSAConfig",
                 "Lfm2Config", "SdarConfig"]
    max_batch: int = 4            # concurrent decode rows
    max_len: int = 512            # per-request logical cap
    page_size: int = 16
    num_pages: int = 256          # pool capacity = num_pages * page_size
    prefill_buckets: Tuple[int, ...] = (32, 64, 128, 256)
    temperature: float = 0.0
    eos_token: Optional[int] = None
    seed: int = 0
    # continuous batching: prefill chunks advanced per scheduler tick
    # (bounds how much prefill compute a tick may steal from decode)
    prefill_decode_ratio: int = 1

    @property
    def pages_per_seq(self) -> int:
        if _windowed(self.model):
            # a padded last chunk may run a bucket past max_len
            return self.model.prefill_pages(
                self.max_len + self.prefill_buckets[-1], self.page_size)
        return -(-self.max_len // self.page_size)


def _module_of(cfg):
    return LlamaModel(cfg) if isinstance(cfg, LlamaConfig) else cfg.module()


def _recurrent(cfg) -> bool:
    """Whether a row of this model carries recurrent state (a scan
    layer's) beside its K/V pages."""
    return hasattr(cfg, "state_shapes")


def _windowed(cfg) -> bool:
    """Whether a row of this model keeps something other than the K/V of
    its whole context in its pages (summaries of closed windows beside the
    open one), so that pages leave a row while it lives."""
    return hasattr(cfg, "window_closes")


def _latent(cfg) -> bool:
    """Whether a layer of this model caches one latent row a token that is
    its key and its value (multi-head latent attention): one pool a layer."""
    return hasattr(cfg, "latent_cache")


def _indexed(cfg) -> bool:
    """Whether a layer of this model scores its cached tokens by an indexer
    and attends the selected ones alone (learned sparse attention): an
    index-key pool a layer beside its K and V pools."""
    return hasattr(cfg, "index_cache")


def _pooled(cfg) -> bool:
    """Whether this model lays its own K and V pools and its prefill chunks
    write the row's pages themselves (built for a model whose rows carry
    recurrent state: only that is staged for a prefilling row)."""
    return hasattr(cfg, "page_pool")


def _blockwise(cfg) -> bool:
    """Whether this model generates by diffusion over blocks: a row's step
    carries the positions of its open block, not one token."""
    return hasattr(cfg, "block_length")


def _layer_caches(cfg) -> Tuple[Tuple[bool, bool, bool], ...]:
    """Per layer, what it keeps between calls: (K/V pages, recurrent
    state, accumulators carried through a decode step). A configuration
    that does not say has layers of one kind: all attend, and all scan if
    its rows carry state at all."""
    if hasattr(cfg, "layer_caches"):
        return tuple(cfg.layer_caches())
    return ((True, _recurrent(cfg), False),) * cfg.num_layers


@functools.lru_cache(maxsize=8)
def _param_init(cfg, mesh):
    """(init program, param shardings) of a model on a mesh. Random
    weights are made ON the device(s), already in their final layout, by
    one jitted program — an eager flax init dispatches (and compiles)
    every initializer op by op. Cached, so engines of one configuration
    share one trace and one compile."""
    from ..parallel.mesh import unbox
    model = _module_of(cfg)
    sample = jnp.zeros((1, 8), jnp.int32)
    pshard = None
    if mesh is not None:
        from ..parallel.mesh import DEFAULT_LOGICAL_AXIS_RULES
        from ..parallel.spmd import logical_names_tree, shardings_tree
        names = logical_names_tree(model, jax.random.PRNGKey(0), sample)
        pshard = shardings_tree(names, mesh,
                                dict(DEFAULT_LOGICAL_AXIS_RULES))
    def init_params(rng):
        return unbox(model.init(rng, sample)["params"])

    return jax.jit(init_params, out_shardings=pshard), pshard


# Visits between two settings of the engine's gauges, as many as between two
# flushes of the `tick` accumulator: the metrics flusher samples a gauge
# every 5 s, and `shared_pages` walks the whole radix (0.8 ms on a full one).
_GAUGES_EVERY = 16


def _no_phase(*names: str):
    """`StepTimer.phase` / `.part` for a caller outside any timer."""
    return contextlib.nullcontext()


def _no_poll():
    """`DryWatch.poll` for an engine that keeps no watch (the accel
    plane's kill switch)."""


def pool_copies(compiled_text: str, pool_shape) -> int:
    """`copy` ops of a compiled program (`compiled.as_text()`) whose
    result has the shape of one whole pool (a page pool's, a state
    pool's): each moves the pool to another layout or memory. Indexed by
    (page, offset) alone, the decode token's write cost four a layer a
    tick (PERF.md, PR 29)."""
    dims = ",".join(map(str, pool_shape))
    return len(re.findall(
        rf"= \w+\[{dims}\]\S* copy(?:-done)?\(", compiled_text))


def array_shapes(compiled_text: str, shape) -> int:
    """Arrays of `shape` (whatever their type and layout) named anywhere
    in a compiled program's text. A prefill chunk that applies the head
    to one row holds none of `[chunk, vocab]`."""
    dims = ",".join(map(str, shape))
    return len(re.findall(rf"\w+\[{dims}\]", compiled_text))


def chunk_logits(model, params, hidden, last):
    """The head in a prefill chunk, over the final norm's output `hidden`
    [1, chunk, hidden size]. `last`, an int32 scalar, is the row of the
    prompt's final token if this chunk holds it and -1 otherwise: the
    logits of that one row, [1, vocab] float32 (the first token is
    sampled from them), and zeros from a chunk that finishes nothing,
    which then neither runs the head nor reads its weights (a `cond`:
    one program a bucket either way). `last=None` is the form before PR
    40, logits at every position [1, chunk, vocab] float32, which the
    tick no longer runs; both forms share every line in front of the
    head and differ in the rows that meet it."""
    def head(rows):
        return model.apply({"params": params}, rows,
                           method="head").astype(jnp.float32)

    if last is None:
        return head(hidden)
    row = jax.lax.dynamic_slice_in_dim(
        hidden, jnp.maximum(last, 0), 1, axis=1)
    return jax.lax.cond(
        last >= 0, lambda: head(row)[:, 0],
        lambda: jnp.zeros((hidden.shape[0], model.config.vocab_size),
                          jnp.float32))


@functools.partial(jax.jit, static_argnames=("sampled",))
def first_token(tokens, last, slot, rng, temperature, top_k, top_p,
                sampled):
    """The token a prompt's prefill ends in, from its last position's
    logits `last` ([1, vocab], as the prompt's last `chunk_prefill`
    returned them; the [1] parameter vectors are the request's), into
    row `slot` of the engine's token vector: the first
    token reaches the host with the next read of the vector, not through
    a fetch of its own. `sampled`: the request has a temperature, so the
    token is drawn as `decode_step` draws; the argmax otherwise, in a
    program of its own (the sampler's program holds a sort of the
    vocabulary even where no batch takes that branch, and compiling that
    sort takes the TPU's compiler 10-35 s)."""
    if sampled:
        token = sample_tokens(rng, last, temperature, top_k, top_p)
    else:
        token = jnp.argmax(last, axis=-1)
    return tokens.at[slot].set(token[0].astype(jnp.int32))


class PagePool:
    """Physical page allocator with refcounts (shared prefix pages)."""

    def __init__(self, num_pages: int):
        self._free = list(range(num_pages - 1, 0, -1))
        # page 0 is the null page block tables pad with; never allocated
        self.refs = np.zeros(num_pages, np.int32)
        self.refs[0] = 1

    def alloc(self) -> Optional[int]:
        if not self._free:
            return None
        page = self._free.pop()
        self.refs[page] = 1
        return page

    def incref(self, page: int):
        self.refs[page] += 1

    def decref(self, page: int):
        self.refs[page] -= 1
        if self.refs[page] == 0:
            self._free.append(page)

    def num_free(self) -> int:
        return len(self._free)


@dataclasses.dataclass
class _Seq:
    request: Optional[GenerationRequest] = None
    pages: List[int] = dataclasses.field(default_factory=list)
    own_from: int = 0            # pages[:own_from] are shared (prefix)
    length: int = 0              # cached tokens
    generated: List[int] = dataclasses.field(default_factory=list)
    # tokens computed or in flight for this row since admission: the one
    # its prefill ends in, then one a dispatched decode step. `generated`
    # holds those the host has read; `length` counts a dispatched step's
    # token as cached already
    dispatched: int = 0
    cancelled: bool = False
    # continuous-batching state
    phase: str = "decode"        # "prefill" until the prompt is cached
    prompt: List[int] = dataclasses.field(default_factory=list)
    # tokens generated before a preemption, re-prefilled as prompt
    resume: List[int] = dataclasses.field(default_factory=list)
    prefill_off: int = 0         # prompt tokens cached so far
    dense_caches: Any = None     # in-flight chunked-prefill cache
    # logits at the prompt's last position, [1, vocab] float32 on the
    # device: what the chunk that finished the prompt returned (or another
    # engine shipped); None until then and after `first_token` took it
    last_logits: Any = None
    admit_at: int = 0            # admission order (preemption picks max)
    # a row of a `_blockwise` model. `length` is its COMMITTED length (a
    # commit forward dispatched counts): the positions in flight are the
    # open block's, `block_at` .. `block_at` + block_length - 1 (-1: no block
    # is open). `block_tail`: the prompt's last `len % block_length` tokens,
    # which open the first block as fixed ids (`block_fixed` of them in the
    # open block). `block_masks`: the masks the block holds after the
    # forwards dispatched, as the static rule counts them (the dynamic rule
    # may be ahead: `_emit_blocks`); `block_step`: denoising forwards
    # dispatched in it; `block_take`: the tokens it hands out; `block_last`:
    # no block opens behind it; `block_counted`: its tokens are in
    # `dispatched`; `block_rule`: the request's (denoising steps,
    # threshold). `flight`: per forward dispatched and not yet read,
    # (block_at, fixed, take, last, masks believed left)
    block_at: int = -1
    block_tail: List[int] = dataclasses.field(default_factory=list)
    block_fixed: int = 0
    block_masks: int = 0
    block_step: int = 0
    block_take: int = 0
    block_last: bool = False
    block_counted: bool = False
    block_rule: Tuple[int, float] = (1, 2.0)
    block_opened_ts: float = 0.0
    blocks_done: int = 0
    flight: "collections.deque" = dataclasses.field(
        default_factory=collections.deque)


class PagedLLMEngine:
    """The serve path's engine: submit/step/generate/stats, cancel() and
    per-token streaming callbacks.

    Tensor parallelism: pass `mesh` (a jax Mesh with a `tensor` axis) and
    params + KV pages are sharded over it — params by their flax logical
    axes (heads/kv_heads/mlp/vocab -> tensor), pages on the kv_heads dim
    — so models larger than one chip's HBM serve across chips. The page
    table and scheduler stay host-side and see only logical page ids
    (reference: TP×PP engine-worker placement in
    llm/_internal/serve/deployments/llm/vllm/vllm_models.py:169-178,251;
    here TP is a mesh axis and GSPMD/shard_map insert the collectives)."""

    def __init__(self, config: PagedEngineConfig,
                 params: Optional[Any] = None, mesh=None):
        self.config = config
        cfg = config.model
        self.model = _module_of(cfg)
        self.mesh = mesh
        self._tp = int(mesh.shape.get("tensor", 1)) if mesh is not None \
            else 1
        if self._tp > 1 and _recurrent(cfg):
            raise NotImplementedError(
                "recurrent state over a tensor mesh is not built: the "
                "state pool is not sharded")
        # rows whose pages hold summaries of closed windows (`_windowed`)
        self._windowed = _windowed(cfg)
        if self._windowed:
            if self._tp > 1:
                raise NotImplementedError(
                    "compressed windows over a tensor mesh are not built: "
                    "the chunk over pages and the compression are not "
                    "mapped over the heads")
            cfg.check_pages(config.page_size, config.prefill_buckets)
        # layers that cache one latent row a token (`_latent`)
        self._latent = _latent(cfg)
        if self._latent and self._tp > 1:
            raise NotImplementedError(
                "latent attention over a tensor mesh is not built: the "
                "heads would be split and the latent pool replicated")
        # layers that keep an index-key pool beside K and V (`_indexed`)
        self._indexed = _indexed(cfg)
        if self._indexed and self._tp > 1:
            raise NotImplementedError(
                "sparse attention over a tensor mesh is not built: the "
                "selection is a row's, and the token-major pools are not "
                "split over the kv heads")
        # models whose prefill chunks write the row's pages themselves, and
        # whose decode step takes `_row_pools` and the counters donated
        self._in_place = self._latent or self._indexed
        # a state-carrying model whose chunks write the row's pages too,
        # in pools of its own shape (`_pooled`)
        self._pooled = _pooled(cfg)
        if self._pooled:
            if not _recurrent(cfg) or self._windowed or self._in_place:
                raise NotImplementedError(
                    "pools of a model's own shape are built for a model "
                    "whose rows carry recurrent state and nothing else")
            ps_, buckets = config.page_size, config.prefill_buckets
            if buckets[-1] % ps_ or any(b % ps_ and ps_ % b
                                        for b in buckets):
                raise ValueError(
                    f"prefill buckets {buckets} are not each whole pages "
                    f"of {ps_} or a part of one")
        # rows whose step carries the positions of an open block
        self._blockwise = _blockwise(cfg)
        if self._blockwise:
            if self._tp > 1:
                raise NotImplementedError(
                    "generation by diffusion over blocks over a tensor mesh "
                    "is not built: the block step's pools and counters are "
                    "not sharded")
            L = cfg.block_length
            if config.page_size % L or config.max_len % L \
                    or any(b % L for b in config.prefill_buckets):
                raise ValueError(
                    f"page_size {config.page_size}, max_len "
                    f"{config.max_len} and the prefill buckets "
                    f"{config.prefill_buckets} are not each whole blocks of "
                    f"{L} positions")
        if self._tp > 1:
            if cfg.num_kv_heads % self._tp or cfg.num_heads % self._tp:
                raise ValueError(
                    f"num_heads={cfg.num_heads}/num_kv_heads="
                    f"{cfg.num_kv_heads} not divisible by tensor axis "
                    f"size {self._tp}")
        rng = jax.random.PRNGKey(config.seed)
        self._page_sharding = None
        self._dense_sharding = None
        init, pshard = _param_init(cfg, mesh)
        if mesh is not None:
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as PSpec
            # pages: [kv_heads, pages, page_size, hd] sharded on kv_heads
            self._page_sharding = NamedSharding(mesh, PSpec("tensor"))
            # dense prefill caches: [1, kv_heads, L, hd]
            self._dense_sharding = NamedSharding(mesh, PSpec(None, "tensor"))
        if params is None:
            params = init(rng)
        elif pshard is not None:
            # Params from a single-device engine or a checkpoint:
            # scatter to the mesh layout.
            params = jax.device_put(params, pshard)
        self.params = params
        # the buckets whose chunks take the routed experts' sorted form
        # (`models.moe.sorted_form`, a function of the bucket and the
        # experts' shapes: what the chunk's program did at trace time);
        # None for a model without routed experts
        self._sorted_buckets = sorted_buckets(params, config.prefill_buckets)
        self._rng = rng
        kvh, hd = cfg.num_kv_heads, cfg.head_dim_
        P, ps = config.num_pages, config.page_size
        # the attention path the decode program is built with here
        reference = cfg.attention_impl == "reference"
        self._paged_kernel = latent_kernel(
            cfg.latent_cache()[1], reference) if self._latent \
            else sparse_kernel(reference, ps, cfg.index_cache()) \
            if self._indexed \
            else paged_kernel(hd, reference, cfg.page_pool(P, ps)[-1]
                              if self._pooled else hd)
        # kernel layout: [kv_heads, num_pages, page_size, head_dim]; the
        # selected tokens' gather wants a token's kv heads in one row; a
        # model that lays its own says how
        shape = (1, P, ps, kvh * hd) if self._indexed \
            else cfg.page_pool(P, ps) if self._pooled else (kvh, P, ps, hd)
        def _zero_pages():
            z = jnp.zeros(shape, cfg.dtype)
            if self._page_sharding is not None:
                z = jax.device_put(z, self._page_sharding)
            return z
        # a page pool per layer that attends
        attending = sum(1 for attends, _, _ in _layer_caches(cfg) if attends)
        self.k_pages = [_zero_pages() for _ in range(attending)]
        # a latent row is key and value at once: no second pool
        self.v_pages = [] if self._latent \
            else [_zero_pages() for _ in range(attending)]
        # and an index key a token beside them, for a model that selects
        self.index_pages = [
            jnp.zeros((1, P, ps, cfg.index_cache()), cfg.dtype)
            for _ in range(attending)] if self._indexed else []
        # recurrent state beside the pages, for a model that has it: per
        # layer that scans, a tuple of pools of max_batch rows (what
        # `state_shapes()` names: (conv, ssm), or (conv,)), row = slot
        # index (a slot that is not decoding is masked out of the decode
        # step, and an install overwrites a row whole); None otherwise
        self.state = cfg.init_state(config.max_batch) \
            if _recurrent(cfg) else None
        # accumulators a model carries through the decode step (an expert
        # layer's per-expert counts): donated to it and returned by it, so
        # only the stepping thread may touch them, and only between steps
        # (`read_counters`); stats() reads the host copy published there.
        # None for a model without them
        self.counters = cfg.init_counters() \
            if hasattr(cfg, "init_counters") else []
        self._counters_host = jax.device_get(self.counters)
        self._counters_at = 0          # `_steps` when that copy was made
        self._counters_asked = False
        # the same accumulators of a `_blockwise` model's prefill chunks of
        # the largest bucket (tokens routed to each held expert, chunks
        # that routed it any), donated to the chunk beside the pools;
        # `_chunks_counted`: how many such chunks were dispatched
        self.chunk_counters = cfg.init_counters() \
            if _blockwise(cfg) else []
        self._chunk_counters_host = jax.device_get(self.chunk_counters)
        self._chunks_counted = 0
        # (slot, staged state) of prefills finished this tick, installed
        # in the tick's `state` phase
        self._state_due: List[Tuple[int, Any]] = []
        self._state_installs = 0
        self._prefix_skipped_recurrent = 0
        # prefill chunks of a `_pooled` model: each wrote the row's pages
        self._prefill_chunks_in_place = 0
        # windows compressed (by the phase the row was in), the pages that
        # gave back, prompts whose prefix was not looked up because a page
        # of this model is no prefix's K/V once its window has closed, and
        # the rows of each kind the decode steps attended (`_windowed`)
        self._window_closes = {"prefill": 0, "decode": 0}
        self._pages_released = 0
        self._prefix_skipped_compressed = 0
        self._summary_rows = 0
        self._window_rows = 0
        # what the latent path did (`_latent`): cached rows the decode
        # steps attended; pages the decoding rows held a step, counted a
        # row, counted once (rows on one document share pages) and as the
        # kernel's schedule has it copy them (a group's shared span once:
        # between the two); prompt
        # tokens mapped from the radix and computed; cached rows the
        # prefill chunks attended; radix nodes evicted
        self._latent_rows_attended = 0
        self._latent_pages_rowwise = 0
        self._latent_pages_distinct = 0
        self._latent_pages_copied = 0
        # what the sparse path did (`_indexed`): cached index keys the
        # decode steps scored (a row a step, whatever the layers); pages of
        # them counted a row and once (rows on one document score the same
        # pages); tokens the steps selected, sum of min(context, topk), and
        # the contexts they selected from
        self._index_rows_scanned = 0
        self._index_pages_rowwise = 0
        self._index_pages_distinct = 0
        self._sparse_rows_selected = 0
        self._sparse_rows_context = 0
        self._page_seen = np.zeros((P,), bool)
        self._prefix_shared_tokens = 0
        self._prefill_computed_tokens = 0
        self._prefill_ctx_rows = 0
        self._radix_evictions = 0
        self.pool = PagePool(P)
        self.radix = RadixPrefixCache(
            self.pool, ps, max_entries=int(CONFIG.prefix_cache_entries))
        # waiting queue: _pending is the thread-safe ingress; the tick
        # drains it into _parked, which also receives preempted requests
        # at its FRONT (they re-admit first)
        self._parked: "collections.deque" = collections.deque()
        self._admit_clock = 0
        self._preemptions = 0
        # recent TTFTs feed autoscaling_metrics() (median over a window)
        self._recent_ttfts: "collections.deque" = collections.deque(
            maxlen=64)
        self._prefix_hits = 0
        self._prefix_misses = 0
        self.seqs: List[_Seq] = [_Seq() for _ in range(config.max_batch)]
        self._pending: "queue.Queue[GenerationRequest]" = queue.Queue()
        self._by_id: Dict[str, _Seq] = {}
        self._steps = 0
        self._tokens_generated = 0
        # One decode step of lookahead. `_tokens` is the token vector on
        # the device, row = slot: the last dispatched decode step's
        # output, with the token a finished prefill ends in written at
        # its row since. It is the next step's input as it stands and
        # makes no trip through the host. `_unread` lists the (slot, seq)
        # whose newest token is in it and has not been read.
        self._tokens = jnp.zeros((config.max_batch,), jnp.int32)
        if self._blockwise:
            # a `_blockwise` model's is the report of its last block step,
            # [rows, block_length + 2]: every row's block ids as the step
            # left them (the next step's input, as it stands), the masks the
            # step found in the block and the masks it left
            self._tokens = jnp.zeros(
                (config.max_batch, cfg.block_length + 2), jnp.int32)
        # what the block steps did (`_blockwise`): row-forwards dispatched,
        # those of them that were commits, tokens handed out, blocks the
        # dynamic rule finished ahead of the static count
        self._block_forwards = 0
        self._commit_forwards = 0
        self._block_tokens_out = 0
        self._blocks_early = 0
        self._block_metered = (0, 0)   # of the first two, in the metrics
        if mesh is not None:
            self._tokens = jax.device_put(
                self._tokens, NamedSharding(mesh, PSpec()))
        self._unread: List[Tuple[int, _Seq]] = []
        # what the step is told about its rows (tables, lengths, sampling
        # triples, the rows that decode), kept between visits on the host
        # and on the device: only what changed is written or sent
        self._stage = StagedRows(config.max_batch, config.pages_per_seq)
        # requests that ended since step() last returned
        self._finished: List[Tuple[GenerationRequest, Any]] = []
        # decode steps dispatched before the tokens of the step before
        # were read / reads with nothing dispatched behind them, by reason
        # / tokens computed for a row found finished or cancelled a tick
        # late (dropped: never emitted, never counted)
        self._lookahead_ticks = 0
        self._drained_ticks: Dict[str, int] = {}
        self._discarded_tokens = 0
        # what the visits dispatched (the `tick` row's counters)
        self._decode_rows = 0
        # decode steps dispatched, by the branch `sample_tokens` takes in
        # each (`sampling.SAMPLER_TIERS`)
        self._sampler_steps = [0] * len(SAMPLER_TIERS)
        self._prefill_chunks = 0
        self._prefill_chunks_sorted = 0     # of them, on the sorted form
        self._prefill_heads = 0     # chunks that ran the head (on one row)
        self._prompts_finished = 0
        # accelerator-plane step telemetry (StepTimer on the decode
        # tick): decode forward ≈ 2 FLOPs per param per token. Checked
        # once here so a killed plane costs the tick two attribute
        # loads, nothing more.
        self._accel = _accel if not _accel.accel_disabled() else None
        if self._accel is not None:
            # listeners precede this engine's prefill/decode compiles
            _accel.ensure_installed()
        # per-tick timings fold locally and flush one aggregated report
        # every 16 ticks — the tick itself pays a perf_counter pair
        self._step_accum = _accel.StepAccumulator("decode") \
            if self._accel is not None else None
        # the whole continuous tick by phase (kind "tick"; "decode" above
        # keeps its extent, dispatch to last callback, inside it)
        self._tick_accum = _accel.StepAccumulator("tick", timeline=True) \
            if self._accel is not None else None
        # the account of a dry device (README, "Tick phases"): polled
        # before and set after every program this engine dispatches
        # (`_dispatching` / `_dispatched`), where a phase of the tick ends
        # and inside the stepping thread's long loops (the radix's)
        self._dry = _accel.DryWatch() if self._accel is not None else None
        # one poll of it, from inside the stepping thread's loops
        self._poll = self._dry.poll if self._dry is not None else _no_poll
        if self._dry is not None:
            self.radix.poll = self._poll
        # perf_counter at the last tick's end if work was left then:
        # the next tick's `between` runs from it
        self._tick_end: Optional[float] = None
        self._num_params = sum(
            int(np.prod(p.shape))
            for p in jax.tree_util.tree_leaves(self.params))
        # the parameters a position's forward multiplies by (a `_blockwise`
        # model's step is timed by them; its configuration counts them)
        self._active_params = cfg.active_params() if self._blockwise \
            else self._num_params
        model = self.model
        page_sharding = self._page_sharding

        def decode_step(params, k_pages, v_pages, block_tables, lengths,
                        tokens, rng, temperature, top_k, top_p):
            """`tokens`: the vector this step's `out` replaces, [rows] on
            the device: what the step before sampled for each row."""
            caches = [
                {"k": k_pages[i], "v": v_pages[i],
                 "block_tables": block_tables, "lengths": lengths}
                for i in range(cfg.num_layers)
            ]
            logits, new_caches = model.apply(
                {"params": params}, tokens[:, None],
                positions=lengths[:, None],
                kv_caches=caches, cache_index=None)
            last = logits[:, -1, :].astype(jnp.float32)
            out = sample_tokens(rng, last, temperature, top_k, top_p)
            nk = [c["k"] for c in new_caches]
            nv = [c["v"] for c in new_caches]
            if page_sharding is not None:
                # pin the updated pools to the kv-head sharding so the
                # donated-buffer layout is stable across steps
                nk = [jax.lax.with_sharding_constraint(a, page_sharding)
                      for a in nk]
                nv = [jax.lax.with_sharding_constraint(a, page_sharding)
                      for a in nv]
            return out.astype(jnp.int32), nk, nv

        self._decode = jax.jit(decode_step, donate_argnums=(1, 2))

        def chunk_prefill(params, tokens, positions, dense_caches, offset,
                          last=None):
            """One prefill chunk: write K/V for `tokens` into the dense
            caches at `offset`, attend causally over everything cached so
            far. Chunked prefill lifts the prompt cap to max_len — any
            prompt runs as ceil(n/bucket) chunks of one compiled shape
            per bucket (reference: vLLM chunked prefill, delegated by
            llm/_internal/serve/deployments/llm/vllm/). Returns the
            logits of row `last` alone, [1, vocab] float32 (`chunk_logits`:
            zeros, and no head, at -1); called without `last`, the logits
            of every position [1, chunk, vocab], a specialisation of its
            own that only the benchmark's parity check still compiles."""
            hidden, new_caches = model.apply(
                {"params": params}, tokens, positions=positions,
                kv_caches=dense_caches, cache_index=offset, head=False)
            return chunk_logits(model, params, hidden, last), new_caches

        self._chunk_prefill = jax.jit(chunk_prefill, donate_argnums=(3,))

        def _dense_zero_caches():
            # Length covers the worst chunked-prefill write: the last
            # chunk is bucket-rounded, so a prompt ending near max_len
            # writes up to (largest_bucket - 1) tokens of padding past
            # it. Without the slack, dynamic_update_slice would CLAMP
            # the start index and silently corrupt earlier positions.
            slack = config.prefill_buckets[-1]
            return init_kv_caches(
                cfg, 1, config.pages_per_seq * config.page_size + slack)

        self._dense_zero_caches = jax.jit(
            _dense_zero_caches,
            out_shardings=self._dense_sharding)  # None = default

        def write_pages(k_pages, v_pages, dense_caches, page_ids,
                        start_tok):
            """Scatter pages of a [1, kvh, L, hd] dense prefill cache
            into the pools at physical ids `page_ids`, starting at token
            offset `start_tok`. `page_ids` is padded to pages_per_seq
            with the null page so there is ONE compiled shape per
            dense-cache length (a per-sequence page count would compile
            a program per distinct tail size); clamped gathers send the
            pad lanes' garbage to the reserved null page, never a live
            one."""
            ps_ = config.page_size
            n = page_ids.shape[0]
            nk, nv = [], []
            for (kp, vp, (dk, dv)) in zip(k_pages, v_pages, dense_caches):
                # [1, kvh, L, hd] -> [kvh, n, ps, hd] page-major rows
                idx = start_tok + jnp.arange(n * ps_, dtype=jnp.int32)
                idx = jnp.minimum(idx, dk.shape[2] - 1)
                seg_k = jnp.take(dk[0], idx, axis=1)
                seg_v = jnp.take(dv[0], idx, axis=1)
                kvh_ = seg_k.shape[0]
                seg_k = seg_k.reshape(kvh_, n, ps_, -1)
                seg_v = seg_v.reshape(kvh_, n, ps_, -1)
                uk = kp.at[:, page_ids].set(seg_k.astype(kp.dtype))
                uv = vp.at[:, page_ids].set(seg_v.astype(vp.dtype))
                if page_sharding is not None:
                    uk = jax.lax.with_sharding_constraint(uk, page_sharding)
                    uv = jax.lax.with_sharding_constraint(uv, page_sharding)
                nk.append(uk)
                nv.append(uv)
            return nk, nv

        self._write_pages = jax.jit(write_pages, donate_argnums=(0, 1),
                                    static_argnums=())
        dense_sharding = self._dense_sharding

        def gather_pages(k_pages, v_pages, dense_caches, page_ids):
            """Inverse of write_pages: copy pooled pages into the head
            of a dense prefill cache, so a radix-shared prefix span is
            attended over without recomputing it (zero prefill FLOPs
            for the span). `page_ids` is padded to pages_per_seq with
            the null page for a single compiled shape; padded garbage
            lands at or after the first real tail position, so it is
            either overwritten by the tail chunks or causally masked."""
            out = []
            for (kp, vp, (dk, dv)) in zip(k_pages, v_pages, dense_caches):
                kvh_ = kp.shape[0]
                seg_k = kp[:, page_ids].reshape(
                    kvh_, -1, kp.shape[-1])[None]
                seg_v = vp[:, page_ids].reshape(
                    kvh_, -1, vp.shape[-1])[None]
                ndk = jax.lax.dynamic_update_slice_in_dim(
                    dk, seg_k.astype(dk.dtype), 0, axis=2)
                ndv = jax.lax.dynamic_update_slice_in_dim(
                    dv, seg_v.astype(dv.dtype), 0, axis=2)
                if dense_sharding is not None:
                    ndk = jax.lax.with_sharding_constraint(
                        ndk, dense_sharding)
                    ndv = jax.lax.with_sharding_constraint(
                        ndv, dense_sharding)
                out.append((ndk, ndv))
            return out

        self._gather_pages = jax.jit(gather_pages, donate_argnums=(2,))
        if self.state is not None:
            self._recurrent_programs()
        if self._windowed:
            self._window_programs()
        if self._latent:
            self._latent_programs()
        if self._indexed:
            self._indexed_programs()
        if self._blockwise:
            self._block_programs()

    @property
    def _row_pools(self):
        """What an `_in_place` model's programs take donated and hand
        back: a latent model's one pool a layer, an indexed model's three."""
        if self._indexed:
            return (self.k_pages, self.v_pages, self.index_pages)
        return self.k_pages

    @_row_pools.setter
    def _row_pools(self, pools):
        if self._indexed:
            self.k_pages, self.v_pages, self.index_pages = pools
        else:
            self.k_pages = pools

    def _indexed_programs(self):
        """The programs of a model whose layers keep an index-key pool
        beside K and V (`_indexed`). The decode step takes the three pools
        a layer and the expert counters donated; a prefill chunk takes the
        pools donated and the row's block table in place of a dense cache,
        and is told how many of its tokens are real. `_dense_zero_caches`,
        `_write_pages` and `_gather_pages` stay what the dense engine
        builds and are never called: a shared prefix is scored and attended
        where it lies."""
        model = self.model

        def by_kind(new):
            """A model's per-layer tuples (k, v, index, counters...) as
            the three lists of pools and the counters."""
            return (tuple([kept[i] for kept in new] for i in range(3)),
                    [tuple(kept[3:]) for kept in new if len(kept) > 3])

        def decode_step(params, pools, active, block_tables, lengths,
                        tokens, rng, temperature, top_k, top_p,
                        counters=()):
            caches = [
                {"k": k, "v": v, "index": index, "active": active,
                 "block_tables": block_tables, "lengths": lengths,
                 "pairs": pairs, "steps": steps}
                for k, v, index, (pairs, steps) in zip(*pools, counters)]
            logits, new = model.apply(
                {"params": params}, tokens[:, None],
                positions=lengths[:, None], kv_caches=caches,
                cache_index=None)
            last = logits[:, -1, :].astype(jnp.float32)
            out = sample_tokens(rng, last, temperature, top_k, top_p)
            return (out.astype(jnp.int32),) + by_kind(new)

        self._decode = jax.jit(decode_step, donate_argnums=(1, 10))

        def chunk_prefill(params, tokens, positions, pools, offset, table,
                          valid, last=None):
            """One prefill chunk of one row over its pages. `pools`: (k,
            v, index) pools, a list a kind; `table` [pages_per_seq] the
            row's page ids, shared prefix pages first, the null page where
            it holds none. The chunk's first `valid` rows are written into
            the row's pages, scored against everything cached before them
            and attended under each query's threshold. `last` and the
            logits returned: as the dense `chunk_prefill`'s."""
            hidden, new = model.apply(
                {"params": params}, tokens, positions=positions,
                kv_caches=[{"k": k, "v": v, "index": index, "table": table}
                           for k, v, index in zip(*pools)],
                cache_index=offset, valid=valid, head=False)
            return chunk_logits(model, params, hidden, last), by_kind(new)[0]

        self._chunk_prefill = jax.jit(chunk_prefill, donate_argnums=(3,))

    def _block_programs(self):
        """The programs of a model that generates by diffusion over blocks
        (`_blockwise`). ONE block step serves a denoising forward and a
        commit alike: every live row's open block (its ids held on the
        device between steps, in the report the step before returned) is
        forwarded at positions `lengths` .. `lengths` + block_length - 1,
        its K/V rows written into the row's pages there (over what the
        forward before wrote: a denoising forward's K/V are not kept, and
        the forward of a block without a mask is its commit), attended with
        block_length queries a row over `lengths` + block_length positions,
        and the rule applied on the device to the logits of all rows' block
        positions. A prefill chunk takes the pools donated and the row's
        block table, writes and attends the row's pages under the block mask
        and returns no logits: nothing is sampled from a prompt.
        `_dense_zero_caches`, `_write_pages` and `_gather_pages` stay what
        the dense engine builds and are never called: a shared prefix is
        attended where it lies (whole pages are whole blocks, and a block's
        K/V depend on nothing behind it)."""
        model, cfg = self.model, self.config.model
        L, mask_id = cfg.block_length, cfg.mask_token_id

        def block_caches(k_pages, v_pages, counters, live, block_tables,
                         lengths):
            """What each layer is handed in a block step."""
            return [{"k": k, "v": v, "active": live,
                     "block_tables": block_tables, "lengths": lengths,
                     "pairs": pairs, "steps": steps}
                    for k, v, (pairs, steps)
                    in zip(k_pages, v_pages, counters)]

        def by_kind(new):
            """A model's per-layer tuples (k, v, counters...) as the two
            lists of pools and the counters."""
            return ([kept[0] for kept in new], [kept[1] for kept in new],
                    [tuple(kept[2:]) for kept in new if len(kept) > 2])

        # for a caller that applies the model itself (the benchmark's
        # parity check reads logits where the engine's step returns ids)
        self._by_kind, self._block_caches = by_kind, block_caches

        def decode_step(params, k_pages, v_pages, live, block_tables,
                       lengths, report, opened, fresh, count, threshold,
                       rng, temperature, top_k, top_p, counters):
            """`report` [rows, block_length + 2]: what the step before
            returned (this step's returns it anew). `opened` [rows]: the
            row opens a block with the ids `fresh` [rows, block_length];
            `count`, `threshold` [rows]: `sampling.unmask_block`'s; the
            sampler's [rows] parameters hold for every position of a row."""
            ids = jnp.where(opened[:, None], fresh, report[:, :L])
            hidden, new = model.apply(
                {"params": params}, ids,
                positions=lengths[:, None] + jnp.arange(L),
                kv_caches=block_caches(k_pages, v_pages, counters, live,
                                       block_tables, lengths),
                cache_index=None, head=False)
            logits = chunk_logits(
                model, params, hidden.reshape(1, -1, hidden.shape[-1]),
                None)[0]                              # [rows * L, vocab]
            each = lambda a: jnp.repeat(a, L)         # noqa: E731
            with jax.named_scope("sdar/confidence"):
                # the mask's own id is never a candidate (a position that
                # took it would read as masked for ever)
                logits = jnp.where(
                    jnp.arange(logits.shape[-1]) == mask_id, -1e30, logits)
                candidates, confidence = sample_with_confidence(
                    rng, logits, each(temperature), each(top_k),
                    each(top_p))
            with jax.named_scope("sdar/unmask"):
                out, before, after = unmask_block(
                    ids, candidates.reshape(ids.shape),
                    confidence.reshape(ids.shape), mask_id, count,
                    threshold)
                out = jnp.where(live[:, None], out, ids)
                report = jnp.concatenate(
                    [out, before[:, None], after[:, None]], axis=1)
            return (report.astype(jnp.int32),) + by_kind(new)

        self._decode = jax.jit(decode_step, donate_argnums=(1, 2, 15))

        largest = self.config.prefill_buckets[-1]

        def chunk_prefill(params, tokens, positions, pools, offset, table,
                          valid):
            """One prefill chunk of one row over its pages. `pools`: (k
            pools, v pools, the chunks' expert counters); `table`
            [pages_per_seq] the row's page ids, shared prefix pages first,
            the null page where it holds none. The chunk's first `valid`
            rows (whole blocks) are written into the row's pages and
            attended there, with everything cached before them, under the
            block mask. A chunk of the largest bucket adds what it routed
            to the counters; a smaller one hands them on. Returns (a
            witness of the chunk's end, [1] float32: no logits, the head is
            not run; the pools)."""
            k_pages, v_pages, counters = pools
            hidden, new = model.apply(
                {"params": params}, tokens, positions=positions,
                kv_caches=[(k, v, table) for k, v in zip(k_pages, v_pages)],
                cache_index=offset, valid=valid, head=False)
            nk, nv, routed = by_kind(new)
            if tokens.shape[1] == largest:
                counters = [(pairs + got, steps + (got > 0).astype(jnp.int32))
                            for (pairs, steps), (got,)
                            in zip(counters, routed)]
            return hidden[:, -1, 0].astype(jnp.float32), (nk, nv, counters)

        self._chunk_prefill = jax.jit(chunk_prefill, donate_argnums=(3,))

    def _latent_programs(self):
        """The programs of a model whose layers cache one latent row a
        token (`_latent`): `k_pages` holds the one pool a layer and
        `v_pages` nothing. The decode step takes the pools and the expert
        counters donated; a prefill chunk takes the pools donated and the
        row's block table in place of a dense cache, and is told how many
        of its tokens are real. `_dense_zero_caches`, `_write_pages` and
        `_gather_pages` stay what the dense engine builds and are never
        called: a shared prefix is attended where it lies."""
        model = self.model
        kinds = _layer_caches(self.config.model)

        def decode_caches(pools, counters, active, block_tables, lengths):
            """What each layer is handed in a paged decode step: the rows
            that attend a shared document together are found ONCE, for
            every layer's kernel."""
            with jax.named_scope("mla/attend"):
                schedule = share_schedule(block_tables, lengths,
                                          self.config.page_size)
            counts_of = iter(counters)
            caches = []
            for pool, (_, _, counts) in zip(pools, kinds):
                cache = {"pool": pool, "active": active,
                         "block_tables": block_tables, "lengths": lengths,
                         "schedule": schedule}
                if counts:
                    cache["pairs"], cache["steps"] = next(counts_of)
                caches.append(cache)
            return caches

        def by_kind(new):
            """A model's per-layer tuples (pool, counters...) as the
            pools and the counters of the layers that count."""
            return ([kept[0] for kept in new],
                    [tuple(kept[1:]) for kept in new if len(kept) > 1])

        def decode_step(params, pools, active, block_tables, lengths,
                        tokens, rng, temperature, top_k, top_p,
                        counters=()):
            logits, new = model.apply(
                {"params": params}, tokens[:, None],
                positions=lengths[:, None],
                kv_caches=decode_caches(pools, counters, active,
                                        block_tables, lengths),
                cache_index=None)
            last = logits[:, -1, :].astype(jnp.float32)
            out = sample_tokens(rng, last, temperature, top_k, top_p)
            return (out.astype(jnp.int32),) + by_kind(new)

        self._decode = jax.jit(decode_step, donate_argnums=(1, 10))

        def chunk_prefill(params, tokens, positions, pools, offset, table,
                          valid, last=None):
            """One prefill chunk of one row over its pages. `pools`: the
            latent pool of every layer; `table` [pages_per_seq] the row's
            page ids, shared prefix pages first, the null page where it
            holds none. The chunk's first `valid` rows are written into
            the row's pages and attended there with everything cached
            before them, a block of pages at a time. `last` and the logits
            returned: as the dense `chunk_prefill`'s."""
            hidden, new = model.apply(
                {"params": params}, tokens, positions=positions,
                kv_caches=[{"pool": pool, "table": table} for pool in pools],
                cache_index=offset, valid=valid, head=False)
            return chunk_logits(model, params, hidden, last), by_kind(new)[0]

        self._chunk_prefill = jax.jit(chunk_prefill, donate_argnums=(3,))

    def _window_programs(self):
        """The programs of a model whose rows keep summaries of closed
        windows (`_windowed`). The decode step is the dense one (the model
        turns `lengths` into rows of the table itself). A prefill chunk
        takes the page pools donated and the row's block table in place of
        a dense cache, and `compress_window` turns one row's full window
        into summaries in every layer. `_dense_zero_caches`,
        `_write_pages` and `_gather_pages` stay what the dense engine
        builds and are never called."""
        model = self.model

        def chunk_prefill(params, tokens, positions, pools, offset, table,
                          last=None):
            """One prefill chunk of one row over its pages. `pools`: (k
            pools, v pools), a pair a layer; `table` [pages_per_seq] the
            row's page ids, the null page where it holds none. The
            chunk's K/V rows are written into the row's pages and
            attended there with what the row already keeps. `last` and
            the logits returned: as the dense `chunk_prefill`'s."""
            k_pages, v_pages = pools
            hidden, new = model.apply(
                {"params": params}, tokens, positions=positions,
                kv_caches=[{"k": k, "v": v, "table": table}
                           for k, v in zip(k_pages, v_pages)],
                cache_index=offset, head=False)
            return chunk_logits(model, params, hidden, last), (
                [c["k"] for c in new], [c["v"] for c in new])

        self._chunk_prefill = jax.jit(chunk_prefill, donate_argnums=(3,))

        def compress_window(params, k_pages, v_pages, pages):
            """`pages` [window / page_size]: the page ids of one row's
            full window, in order. Its summaries replace the first of
            them in every layer's pools."""
            return self.config.model.compress_window_pages(
                params, k_pages, v_pages, pages)

        self._compress_window = jax.jit(compress_window,
                                        donate_argnums=(1, 2))

    def lower_chunk(self, bucket: Optional[int] = None):
        """The prefill chunk of a `_windowed`, `_in_place`, `_pooled` or
        `_blockwise` model lowered at this engine's shapes (the largest
        bucket's unless told), from shapes alone, in the form the tick runs
        (`last` given)."""
        cfg = self.config
        bucket = bucket or cfg.prefill_buckets[-1]
        i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
        params, k_pages, v_pages = self._shapes()
        if self._pooled:
            staged = {"kv": list(zip(k_pages, v_pages)),
                      "state": jax.eval_shape(lambda: cfg.model.init_state(1))}
            return self._chunk_prefill.lower(
                params, i32(1, bucket), i32(1, bucket), staged, i32(),
                i32(cfg.pages_per_seq), i32(), i32())
        if self._blockwise:
            counters = jax.eval_shape(cfg.model.init_counters)
            return self._chunk_prefill.lower(
                params, i32(1, bucket), i32(1, bucket),
                (k_pages, v_pages, counters),
                i32(), i32(cfg.pages_per_seq), i32())
        if self._in_place:
            pools = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                self._row_pools)
            return self._chunk_prefill.lower(
                params, i32(1, bucket), i32(1, bucket), pools, i32(),
                i32(cfg.pages_per_seq), i32(), i32())
        return self._chunk_prefill.lower(
            params, i32(1, bucket), i32(1, bucket), (k_pages, v_pages),
            i32(), i32(cfg.pages_per_seq), i32())

    def lower_compress(self):
        """`compress_window` lowered at this engine's shapes."""
        cfg = self.config
        return self._compress_window.lower(
            *self._shapes(), jax.ShapeDtypeStruct(
                (cfg.model.window_size // cfg.page_size,), jnp.int32))

    def _shapes(self):
        """(weights, k pools, v pools) as shapes: what `lower_chunk` and
        `lower_compress` lower from."""
        like = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)  # noqa: E731
        return (jax.tree_util.tree_map(like, self.params),
                [like(p) for p in self.k_pages],
                [like(p) for p in self.v_pages])

    def _recurrent_programs(self):
        """The programs of a model whose rows carry recurrent state, in
        place of the three above that would not know it: the decode step
        takes and returns the state pools donated beside the page pools,
        a prefill chunk hands the scanning layers' state on in its staging
        pytree and is told how many of its tokens are real, and
        `write_state` installs a finished prefill's state into its slot. A
        layer is handed, and hands back, what its kind keeps
        (`_layer_caches`) and nothing else: `k_pages` / `v_pages` hold a
        pool per layer that attends, `state` a tuple per layer that scans
        (the arrays `state_shapes()` names, in its order: `(conv, ssm)`,
        or a window alone), `counters` a tuple per layer that counts. The
        chunk of a `_pooled` model takes the page pools in its staging
        pytree's "kv" and the row's block table, and writes and attends the
        row's pages where they lie: a prefilling row then stages its state
        and no K/V."""
        config, cfg, model = self.config, self.config.model, self.model
        kinds = _layer_caches(cfg)
        names = tuple(cfg.state_shapes())
        pooled = _pooled(cfg)

        def by_kind(new):
            """A model's per-layer tuples (k, v, state..., counters...),
            each holding its kind's part only, as the four lists."""
            nk, nv, nstate, ncount = [], [], [], []
            for (attends, scans, counts), kept in zip(kinds, new):
                kept = list(kept)
                if attends:
                    nk.append(kept.pop(0))
                    nv.append(kept.pop(0))
                if scans:
                    nstate.append(tuple(kept.pop(0) for _ in names))
                if counts:
                    ncount.append(tuple(kept))
            return nk, nv, nstate, ncount

        def decode_caches(k_pages, v_pages, state, counters, active,
                          block_tables, lengths):
            """What each layer is handed in a paged decode step."""
            pools = iter(zip(k_pages, v_pages))
            states, counts_of = iter(state), iter(counters)
            caches = []
            for attends, scans, counts in kinds:
                cache = {"active": active}
                if attends:
                    k, v = next(pools)
                    cache.update(k=k, v=v, block_tables=block_tables,
                                 lengths=lengths)
                if scans:
                    cache.update(zip(names, next(states)))
                if counts:
                    cache["pairs"], cache["steps"] = next(counts_of)
                caches.append(cache)
            return caches

        def chunk_caches(staged, table=()):
            """What each layer is handed in a prefill chunk (`table`: the
            row's block table behind a `_pooled` model's pools, in a
            1-tuple)."""
            dense, states = iter(staged["kv"]), iter(staged["state"])
            return [(tuple(next(dense)) + table if attends else ())
                    + (tuple(next(states)) if scans else ())
                    for attends, scans, _ in kinds]

        # for a caller that applies the model itself (the benchmark's
        # parity check reads logits where the engine's step returns ids)
        self._by_kind, self._decode_caches = by_kind, decode_caches
        self._chunk_caches = chunk_caches

        def decode_step(params, k_pages, v_pages, state, active,
                        block_tables, lengths, tokens, rng, temperature,
                        top_k, top_p, counters=()):
            caches = decode_caches(k_pages, v_pages, state, counters,
                                   active, block_tables, lengths)
            logits, new = model.apply(
                {"params": params}, tokens[:, None],
                positions=lengths[:, None],
                kv_caches=caches, cache_index=None)
            last = logits[:, -1, :].astype(jnp.float32)
            out = sample_tokens(rng, last, temperature, top_k, top_p)
            return (out.astype(jnp.int32),) + by_kind(new)

        # `counters` is () for a model without any: no argument, no result
        self._decode = jax.jit(decode_step, donate_argnums=(1, 2, 3, 12))

        def chunk(params, tokens, positions, staged, offset, valid, last,
                  table=()):
            hidden, new = model.apply(
                {"params": params}, tokens, positions=positions,
                kv_caches=chunk_caches(staged, table), cache_index=offset,
                valid=valid, head=False)
            nk, nv, nstate, _ = by_kind(new)
            return chunk_logits(model, params, hidden, last), {
                "kv": list(zip(nk, nv)), "state": nstate}

        def chunk_prefill(params, tokens, positions, staged, offset, valid,
                          last=None):
            """One prefill chunk of one row. `staged`: {"kv": dense
            (k, v) per layer that attends, "state": what `state_shapes()`
            names per layer that scans}. Attention overwrites or masks the
            padded tail; a layer that scans (or counts) is told `valid`,
            the count of real tokens, and keeps the rest out of what it
            hands on. `last` and the logits returned: as the dense
            `chunk_prefill`'s (`chunk_logits`)."""
            return chunk(params, tokens, positions, staged, offset, valid,
                         last)

        if pooled:
            def chunk_prefill(params, tokens, positions, staged,  # noqa: F811
                              offset, table, valid, last=None):
                """The chunk of a `_pooled` model: `staged["kv"]` holds the
                (k, v) page POOLS per layer that attends, `table`
                [pages_per_seq] the row's page ids (the null page where it
                holds none). The chunk's first `valid` K/V rows are
                written into the row's pages and attended there with
                everything cached before them; the rest as above."""
                return chunk(params, tokens, positions, staged, offset,
                             valid, last, (table,))

        self._chunk_prefill = jax.jit(chunk_prefill, donate_argnums=(3,))

        def _staging_zero():
            if pooled:
                # the pools stand in for "kv" when a chunk is dispatched
                return {"kv": [], "state": cfg.init_state(1)}
            slack = config.prefill_buckets[-1]   # as _dense_zero_caches
            length = config.pages_per_seq * config.page_size + slack
            shape = (1, cfg.num_kv_heads, length, cfg.head_dim_)
            return {"kv": [(jnp.zeros(shape, cfg.dtype),
                            jnp.zeros(shape, cfg.dtype))
                           for attends, _, _ in kinds if attends],
                    "state": cfg.init_state(1)}

        self._dense_zero_caches = jax.jit(_staging_zero)

        def write_state(state, staged, slot):
            """A finished prefill's state into row `slot` of every pool
            (the slot's earlier occupant is overwritten whole)."""
            return [tuple(jax.lax.dynamic_update_slice_in_dim(
                pool, new.astype(pool.dtype), slot, axis=0)
                for pool, new in zip(pools, news))
                for pools, news in zip(state, staged)]

        self._write_state = jax.jit(write_state, donate_argnums=(0,))

    def lower_decode(self):
        """The decode step lowered at this engine's shapes, from shapes
        alone: the live page pools are neither read nor donated."""
        cfg = self.config
        B = cfg.max_batch

        def like(a):
            return jax.ShapeDtypeStruct(a.shape, a.dtype,
                                        sharding=a.sharding)

        def vec(dtype, *shape):
            return jax.ShapeDtypeStruct((B,) + shape, dtype)

        if self._blockwise:
            L = cfg.model.block_length
            return self._decode.lower(
                jax.tree_util.tree_map(like, self.params),
                [like(p) for p in self.k_pages],
                [like(p) for p in self.v_pages], vec(jnp.bool_),
                vec(jnp.int32, cfg.pages_per_seq), vec(jnp.int32),
                vec(jnp.int32, L + 2), vec(jnp.bool_), vec(jnp.int32, L),
                vec(jnp.int32), vec(jnp.float32),
                jax.ShapeDtypeStruct(self._rng.shape, self._rng.dtype),
                vec(jnp.float32), vec(jnp.int32), vec(jnp.float32),
                jax.tree_util.tree_map(like, self.counters))
        state = () if self.state is None else (
            jax.tree_util.tree_map(like, self.state), vec(jnp.bool_))
        counters = () if self.state is None and not self._in_place else (
            jax.tree_util.tree_map(like, self.counters),)
        # the row's pools and the rows that decode, for a model in place
        pools = (jax.tree_util.tree_map(like, self._row_pools),
                 vec(jnp.bool_)) \
            if self._in_place else ([like(p) for p in self.k_pages],
                                    [like(p) for p in self.v_pages])
        with self._mesh_scope():
            return self._decode.lower(
                jax.tree_util.tree_map(like, self.params), *pools, *state,
                vec(jnp.int32, cfg.pages_per_seq), vec(jnp.int32),
                vec(jnp.int32),
                jax.ShapeDtypeStruct(self._rng.shape, self._rng.dtype),
                vec(jnp.float32),
                vec(jnp.int32), vec(jnp.float32), *counters)

    def decode_program_text(self) -> str:
        """Compiled text of the decode step: the one-shot probe of what
        the backend was really handed (is the Pallas paged-attention
        `tpu_custom_call` in it, or the gather path? is a page pool
        relaid out?). With a persistent compile cache the compile is a
        hit."""
        return self.lower_decode().compile().as_text()

    def pool_copies(self, compiled_text: str) -> int:
        """Whole-pool copies (`pool_copies`) at this engine's pool shape
        as one device holds it (and at the index-key pool's, for a model
        that keeps one). The decode step must hold none."""
        return sum(pool_copies(compiled_text,
                               pool.sharding.shard_shape(pool.shape))
                   for pool in self.k_pages[:1] + self.index_pages[:1])

    def state_copies(self, compiled_text: str) -> int:
        """Whole-pool copies (`pool_copies`) at the shape of the largest
        pool a scanning layer of this engine keeps (0 for a model without
        recurrent state): the scan state where a layer keeps `(conv,
        ssm)`, the convolution's window where it keeps that alone. The
        decode step must hold none: it updates the donated pool in place,
        one read and one write. A window beside a scan state is not
        counted: a few MB a layer, shifted whole every tick, which the TPU
        compiler stages through fast memory."""
        if self.state is None:
            return 0
        largest = max(self.state[0], key=lambda pool: pool.size)
        return pool_copies(compiled_text, largest.shape)

    def _mesh_scope(self):
        """Context for jit calls: marks the serving mesh active so the
        model's attention detects the tensor axis at trace time
        (shard_map over the Pallas/gather kernel)."""
        if self.mesh is None:
            return contextlib.nullcontext()
        from ..parallel.mesh import kernel_mesh
        return kernel_mesh(self.mesh)

    # -- submission / cancel ---------------------------------------------

    def submit(self, request: GenerationRequest,
               done_callback: Optional[Callable] = None,
               token_callback: Optional[Callable] = None):
        n = len(request.prompt_tokens)
        if n >= self.config.max_len:
            raise ValueError("prompt longer than max_len")
        if self._blockwise:
            self._block_settings(request)    # raises on a rule it has not
            if self.config.model.mask_token_id in request.prompt_tokens:
                raise ValueError(
                    "the prompt holds the mask's id: a position that stands "
                    "for a token not yet generated")
        request._done_callback = done_callback  # type: ignore
        request._token_callback = token_callback  # type: ignore
        request._submit_ts = time.monotonic()  # type: ignore
        reqtrace.record(request.request_id, reqtrace.QUEUED,
                        engine="paged", prompt_tokens=n,
                        max_new=request.max_new_tokens,
                        prefilled=hasattr(request, "_prefilled") or None,
                        tenant=getattr(request, "tenant", None),
                        route=getattr(request, "route", None))
        self._pending.put(request)
        llm_metrics().queue_depth.set(self._pending.qsize(),
                                      tags=_GAUGE_TAGS)

    def submit_prefilled(self, request: GenerationRequest, dense_caches,
                         last_logits,
                         done_callback: Optional[Callable] = None,
                         token_callback: Optional[Callable] = None):
        """Submit a request whose prefill ran on ANOTHER engine
        (prefill/decode disaggregation): `dense_caches` are per-layer
        (k, v) arrays trimmed to the prompt's pages, `last_logits` the
        prompt's final-position logits. Admission (page budget, prefix
        sharing) happens on the normal scheduler tick, which installs
        them where a local prefill would have finished its last chunk."""
        self._no_recurrent("submit_prefilled")
        request._prefilled = (dense_caches, last_logits)  # type: ignore
        self.submit(request, done_callback, token_callback)

    def _no_recurrent(self, what: str):
        if self.state is not None:
            raise NotImplementedError(
                f"{what} ships K/V only: a model whose rows carry "
                "recurrent state cannot be prefilled on another engine yet")
        if self._windowed:
            raise NotImplementedError(
                f"{what} ships the K/V of a whole prompt: a model whose "
                "rows keep summaries of closed windows in their pages "
                "cannot be prefilled on another engine yet")
        if self._latent:
            raise NotImplementedError(
                f"{what} ships dense K/V: a model whose layers cache "
                "latent rows in their pages cannot be prefilled on "
                "another engine yet")
        if self._indexed:
            raise NotImplementedError(
                f"{what} ships dense K/V: a model whose layers keep index "
                "keys beside them cannot be prefilled on another engine "
                "yet")
        if self._blockwise:
            raise NotImplementedError(
                f"{what} ships dense K/V and the logits a first token is "
                "sampled from: a model that generates by diffusion over "
                "blocks prefills its pages in place and samples nothing "
                "from a prompt, and cannot be prefilled on another engine "
                "yet")

    def cancel(self, request_id: str) -> bool:
        """Abort a request: frees its slot+pages on the next tick if
        running, or drops it from the queue."""
        seq = self._by_id.get(request_id)
        if seq is not None and seq.request is not None:
            seq.cancelled = True
            return True
        # queued: rebuild the queue without it
        kept = []
        dropped = None
        try:
            while True:
                request = self._pending.get_nowait()
                if request.request_id == request_id and dropped is None:
                    dropped = request
                    continue
                kept.append(request)
        except queue.Empty:
            pass
        for request in kept:
            self._pending.put(request)
        if dropped is None:
            # drained arrivals and preemption-parked requests
            for request in list(self._parked):
                if request.request_id == request_id:
                    try:
                        self._parked.remove(request)
                    except ValueError:
                        break  # admitted concurrently
                    dropped = request
                    break
        if dropped is not None:
            # queued cancellations must still resolve their waiters
            self._end_request(dropped, None, where="queued")
        return dropped is not None

    def has_work(self) -> bool:
        return (not self._pending.empty()) or bool(self._parked) or \
            any(s.request is not None for s in self.seqs)

    def fail_all(self, error: Exception):
        """Resolve every active and queued request with `error` (the
        serving drive loop calls this when step() raises — callers must
        see the failure, not hang on a silently-spinning engine)."""
        for i, seq in enumerate(self.seqs):
            if seq.request is not None:
                self._end_request(seq.request, error, index=i)
        self._unread.clear()
        self._drain_pending()
        while self._parked:
            self._end_request(self._parked.popleft(), error)

    def _end_request(self, request: GenerationRequest, result,
                     index: Optional[int] = None,
                     where: Optional[str] = None):
        """The one way a request leaves the engine. `result` is what its
        waiter receives and names the outcome: the tokens (done), None
        (cancelled, `where` it was) or the exception (error). `index` is
        the slot it holds, if any: pages released, slot reset.

        A row that ends with a decode step still in flight (its EOS or
        its cancellation was seen a tick late) was computed once more:
        that token is dropped here, and the step's stale write to the
        released pages (and state row) lands before any new owner's,
        because the device runs what it is handed in order and every
        write of a new owner is dispatched after this release."""
        if index is not None:
            seq = self.seqs[index]
            self._discarded_tokens += seq.dispatched - len(seq.generated)
            self._release(seq)
            self.seqs[index] = _Seq()
        metrics = llm_metrics()
        if result is None:
            outcome = "cancelled"
            reqtrace.record(request.request_id, reqtrace.CANCELLED,
                            where=where)
        elif isinstance(result, Exception):
            outcome = "error"
            reqtrace.record(request.request_id, reqtrace.FAILED,
                            error=type(result).__name__)
        else:
            outcome = "done"
            reqtrace.record(request.request_id, reqtrace.FINISHED,
                            tokens=len(result))
            submit_ts = getattr(request, "_submit_ts", None)
            if submit_ts is not None:
                metrics.request_latency.observe(
                    time.monotonic() - submit_ts, tags=_TAGS)
        metrics.requests_finished.inc(tags=dict(_TAGS, outcome=outcome))
        callback = getattr(request, "_done_callback", None)
        if callback is not None:
            callback(request, result)

    # -- scheduler tick ----------------------------------------------------

    def step(self) -> List[Tuple[GenerationRequest, Any]]:  # rtpu: hot-loop
        """One visit of the host to the continuous-batching tick, one
        decode step ahead of it. In order: grow pages for, stage and
        dispatch decode step n+1 for the rows known to decode again
        (`_decode_tick`); only then read step n's tokens, emit them and
        release the rows they finish; reap cancellations, fill freed
        slots from the waiting queue (radix prefix match, tail-only
        prefill setup), advance bounded chunked prefill and move finished
        prompts to the decode phase. Every host phase after the dispatch
        runs while the device computes step n+1. A row that finishes its
        prompt joins the next visit's dispatch: its first token is sampled
        on the device into the token vector (`first_token`) and read with
        that visit's tokens.

        What staging step n+1 needs of step n is deterministic (`length +
        1`, the page the token lands in, the finish rules that do not
        look at the token: `_exhausted`). What is not — EOS, a
        cancellation that lands after the dispatch — is seen one tick
        late, and the row's extra token dropped (`_end_request`). Whatever
        needs a token's value on the host first reads the step in flight
        with nothing behind it (`_drain`): page-pressure preemption, and
        a visit with no row left to dispatch. An engine without work has
        nothing unread.

        The accel plane's `tick` row splits the visit by phase (README,
        "Tick phases"): grow / stage / dispatch / wait / emit / reap /
        admit / prefill / state / gauges tile it; `wait` is the fetch of
        the step dispatched one visit earlier; `between` is the time
        since the last visit's end while work was waiting — the serving
        loop's executor hop and whatever else held this thread. Its
        counters say how often the step ahead was there, and how many
        rows and prefill chunks the visit dispatched; the row also has
        the visits' distribution and the slow ones whole (`extent_hist`,
        `slow`: a visit's extent is `between` + the visit)."""
        if self._counters_asked:
            self.read_counters()
        entered = time.perf_counter()
        tick = _accel.StepTimer("tick", sink=self._tick_accum,
                                watch=self._dry)
        if self._tick_end is not None:
            tick.outside("between", entered - self._tick_end)
        before = self._ahead_counts()
        with tick:
            if self._blockwise:
                self._block_tick(tick.phase)
            else:
                self._decode_tick(tick.phase)
            with tick.phase("reap"):
                self._reap_cancelled()
            with tick.phase("admit"):
                self._admit()
            with tick.phase("prefill"):
                self._prefill_tick(tick.part)
            if self._state_due:
                with tick.phase("state"):
                    self._install_states()
            self._steps += 1
            if self._steps % _GAUGES_EVERY == 0:
                with tick.phase("gauges"):
                    self._set_gauges()
            for name, count in self._ahead_counts().items():
                tick.count(name, count - before[name])
        if self.has_work():
            self._tick_end = time.perf_counter()
        else:
            self._tick_end = None
            if self._dry is not None:
                self._dry.idle()
            # drained: flush the partial windows so step telemetry
            # never lags an idle engine by up to `every` ticks
            self._flush_step_rows()
            self.read_counters()
        finished, self._finished = self._finished, []
        return finished

    def read_counters(self):
        """Publish the model's accumulators to stats(): a fetch from the
        device that waits for the step in flight. For the stepping thread
        between steps only (the arrays are donated to every decode step,
        and a reader on another thread would find them deleted): `step`
        calls it when the engine drains and, once, after a stats() call
        found the host copy older than the last step; a caller that runs
        between steps (the benchmark's marks) may call it itself."""
        if self.counters:
            with _accel.pause("read_counters"):
                counters, of_chunks = jax.device_get(  # host-sync ok:
                    (self.counters, self.chunk_counters))   # on request
            self._counters_host = counters
            self._chunk_counters_host = of_chunks
        self._counters_at, self._counters_asked = self._steps, False

    def _ahead_counts(self) -> Dict[str, int]:
        """How the step ahead fared so far: decode steps dispatched
        before the last one's tokens were read, reads with nothing
        dispatched behind them, tokens dropped a tick late; and what the
        visits dispatched: decode rows, the steps and the kept arrays sent
        for them (`stage_steps`, `stage_uploads`), prefill chunks, those of
        them that ran the head, the prompts they finished, and the decode
        steps by the sampler's branch; for a model with routed experts, the
        chunks whose bucket put them on the sorted form
        (`prefill_chunks_sorted`)."""
        counts = {"lookahead_ticks": self._lookahead_ticks,
                  "drained_ticks": sum(self._drained_ticks.values()),
                  "discarded_tokens": self._discarded_tokens,
                  "decode_rows": self._decode_rows,
                  "stage_steps": self._stage.steps,
                  "stage_uploads": self._stage.uploads,
                  **{f"sampler_{tier}_steps": steps for tier, steps
                     in zip(SAMPLER_TIERS, self._sampler_steps)},
                  "prefill_chunks": self._prefill_chunks,
                  "prefill_heads": self._prefill_heads,
                  "prompts_finished": self._prompts_finished}
        if self._sorted_buckets is not None:
            # chunks whose routed experts ran as sorted pairs
            counts["prefill_chunks_sorted"] = self._prefill_chunks_sorted
        if self._blockwise:
            # row-forwards the block steps dispatched (`decode_rows` counts
            # the same), those that were commits, tokens handed out, blocks
            # the dynamic rule finished ahead of the static count; chunks
            # wrote the rows' pages themselves
            counts.update(
                block_forwards=self._block_forwards,
                commit_forwards=self._commit_forwards,
                block_tokens_out=self._block_tokens_out,
                blocks_early=self._blocks_early,
                prefix_shared_tokens=self._prefix_shared_tokens,
                prefill_computed_tokens=self._prefill_computed_tokens,
                prefill_ctx_rows=self._prefill_ctx_rows,
                # chunks of the largest bucket: those `chunk_expert_*` count
                prefill_chunks_largest=self._chunks_counted)
        if self._pooled:
            # chunks that wrote their K/V into the row's pages themselves,
            # the prompt tokens they computed and the cached rows they
            # attended
            counts.update(
                prefill_chunks_in_place=self._prefill_chunks_in_place,
                prefill_computed_tokens=self._prefill_computed_tokens,
                prefill_ctx_rows=self._prefill_ctx_rows)
        if self._windowed:
            # windows compressed and the pages that gave back; the rows of
            # each kind the decode steps attended, from lengths alone
            counts.update(
                window_closes_prefill=self._window_closes["prefill"],
                window_closes_decode=self._window_closes["decode"],
                pages_released=self._pages_released,
                summary_rows=self._summary_rows,
                window_rows=self._window_rows,
                prefix_skipped_compressed=self._prefix_skipped_compressed)
        if self._latent:
            counts.update(
                latent_rows_attended=self._latent_rows_attended,
                latent_pages_rowwise=self._latent_pages_rowwise,
                latent_pages_distinct=self._latent_pages_distinct,
                latent_pages_copied=self._latent_pages_copied)
        if self._indexed:
            counts.update(
                index_rows_scanned=self._index_rows_scanned,
                index_pages_rowwise=self._index_pages_rowwise,
                index_pages_distinct=self._index_pages_distinct,
                sparse_rows_selected=self._sparse_rows_selected,
                sparse_rows_context=self._sparse_rows_context)
        if self._in_place:
            # what the radix gave and what the chunks computed and attended
            counts.update(
                prefix_shared_tokens=self._prefix_shared_tokens,
                prefill_computed_tokens=self._prefill_computed_tokens,
                prefill_ctx_rows=self._prefill_ctx_rows,
                radix_evictions=self._radix_evictions,
                radix_evict_walks=self.radix.walks)
        return counts

    def _dispatching(self):
        """Just before this engine hands the device a program."""
        if self._dry is not None:
            self._dry.dispatching()

    def _dispatched(self, out):
        """Just after: `out`, an array of the program's outputs that
        nothing donates before the next dispatch."""
        if self._dry is not None:
            self._dry.dispatched(out)

    def _flush_step_rows(self):
        """When the engine drains and in `stats()`: the accumulators hand
        their partial window to the accel plane, and the gauges are set
        (`step` sets them every `_GAUGES_EVERY` visits besides)."""
        for accum in (self._step_accum, self._tick_accum):
            if accum is not None:
                accum.flush()
        self._set_gauges()

    def _waiting_count(self) -> int:
        return self._pending.qsize() + len(self._parked)

    def _set_gauges(self):
        metrics = llm_metrics()
        waiting = self._waiting_count()
        metrics.queue_depth.set(waiting, tags=_GAUGE_TAGS)
        metrics.running.set(
            sum(1 for s in self.seqs if s.request is not None),
            tags=_GAUGE_TAGS)
        free = self.pool.num_free()
        metrics.kv_utilization.set(
            1.0 - free / max(1, self.config.num_pages), tags=_GAUGE_TAGS)
        metrics.kv_occupancy.set(self.config.num_pages - 1 - free,
                                 tags=_GAUGE_TAGS)
        metrics.waiting.set(waiting, tags=_GAUGE_TAGS)
        metrics.shared_pages.set(self.radix.shared_pages(),
                                 tags=_GAUGE_TAGS)
        if self._blockwise:
            metrics.masks_in_flight.set(self._masks_in_flight(),
                                        tags=_GAUGE_TAGS)

    def _install_states(self):
        """`write_state` for every prefill that finished this tick."""
        with self._mesh_scope():
            for slot, staged in self._state_due:
                self._dispatching()
                self.state = self._write_state(
                    self.state, staged, jnp.asarray(slot, jnp.int32))
                self._dispatched(self.state[0][0])
                self._state_installs += 1
        self._state_due.clear()

    def _reap_cancelled(self):
        """Release cancelled sequences in ANY phase (a mid-prefill
        cancel must return its pages too) before admission reuses the
        slots."""
        for i, seq in enumerate(self.seqs):
            if seq.request is not None and seq.cancelled:
                self._end_request(seq.request, None, index=i,
                                  where=seq.phase)

    def _drain_pending(self):
        try:
            while True:
                self._parked.append(self._pending.get_nowait())
        except queue.Empty:
            pass

    # -- park bookkeeping (request observatory + park histogram) ---------

    def _compile_total(self) -> float:
        """Disjoint backend-compile seconds so far (the PR-7 tracker);
        0 when the accel plane is killed — compile attribution then
        degrades to zero, it never invents time."""
        return (self._accel.backend_compile_seconds_total()
                if self._accel is not None else 0.0)

    def _park_note(self, request: GenerationRequest, reason: str):
        """Open a park episode ONCE (admission retries every tick while
        pages are short — one PARKED event and one histogram sample per
        episode, not per retry)."""
        if getattr(request, "_rt_park_ts", None) is None:
            request._rt_park_ts = time.monotonic()  # type: ignore
            request._rt_park_reason = reason  # type: ignore
            reqtrace.record(request.request_id, reqtrace.PARKED,
                            reason=reason)

    def _unpark_note(self, request: GenerationRequest) -> float:
        """Close a park episode at (re-)admission: observe the park
        histogram by reason, accumulate per-request park seconds (the
        why_slow park bucket's metric twin), and stamp RESUMED for
        preempted requests. Returns total park seconds so far."""
        park_ts = getattr(request, "_rt_park_ts", None)
        if park_ts is not None:
            parked = time.monotonic() - park_ts
            reason = getattr(request, "_rt_park_reason", "unknown")
            llm_metrics().park_seconds.observe(
                parked, tags=dict(_TAGS, reason=reason))
            request._rt_park_total = parked + \
                getattr(request, "_rt_park_total", 0.0)  # type: ignore
            request._rt_park_ts = None  # type: ignore
            if getattr(request, "_resume_tokens", None):
                reqtrace.record(request.request_id, reqtrace.RESUMED,
                                reason=reason,
                                parked_s=round(parked, 6))
        return getattr(request, "_rt_park_total", 0.0)

    def _admit(self):
        self._drain_pending()
        for index, seq in enumerate(self.seqs):
            if seq.request is not None:
                continue
            if not self._parked:
                return
            request = self._parked.popleft()
            try:
                if not self._begin_prefill(index, request):
                    self._park_note(request, "no_pages")
                    self._parked.appendleft(request)
                    return
                shipped = getattr(request, "_prefilled", None)
                if shipped is None:
                    # chunks of these go straight into the row's pages
                    if not self._windowed and not self._in_place \
                            and not self._blockwise:
                        self._stage_prefill_cache(seq)
                else:
                    # prefilled elsewhere (`submit_prefilled`): enters
                    # where a local prefill would have finished its last
                    # chunk; after a preemption it re-prefills locally
                    del request._prefilled
                    caches, last_logits = shipped
                    seq.last_logits = jnp.asarray(
                        last_logits, jnp.float32)[None, :]
                    seq.dense_caches = [(jnp.asarray(k), jnp.asarray(v))
                                        for (k, v) in caches]
                    seq.prefill_off = len(seq.prompt)
                    self._finish_prefill(index)
            except Exception as e:  # noqa: BLE001
                held = self.seqs[index].request is request
                self._end_request(request, e, index if held else None)

    def _begin_prefill(self, index: int,
                       request: GenerationRequest) -> bool:
        """Admit a request into the prefill phase: radix-match the
        longest cached prefix (mapped copy-on-write into the block
        table) and allocate only the tail prompt pages. Returns False
        when pages are short even after pressure eviction (caller
        re-parks the request)."""
        ps = self.config.page_size
        resume = list(getattr(request, "_resume_tokens", []))
        prompt = list(request.prompt_tokens) + resume
        shared = self._match_prefix(prompt)
        n_prompt_pages = -(-len(prompt) // ps)
        tail_pages = n_prompt_pages - len(shared)
        if self._windowed:
            # the budget is the most the row holds on its way through the
            # prompt; the chunks take their pages as they come to them
            # (`_chunk_pages`), so a window's are back before the next's
            if self.pool.num_free() \
                    < self.config.model.prefill_pages(len(prompt), ps):
                return False
            tail_pages = 0
        if self.pool.num_free() < tail_pages:
            self._radix_evictions += self.radix.evict_pages(
                tail_pages - self.pool.num_free())
            if self.pool.num_free() < tail_pages:
                for page in shared:
                    self.pool.decref(page)
                return False
        new_ids = []
        for _ in range(tail_pages):
            page = self.pool.alloc()
            assert page is not None, "budget checked above"
            new_ids.append(page)
        seq = self.seqs[index]
        seq.request = request
        seq.prompt = prompt
        if self._blockwise:
            # the prompt's whole blocks are prefilled; the rest of it opens
            # the first block as fixed ids. After a preemption `prompt`
            # ends at a block's boundary (tokens are handed out by the
            # block, and the first block closes the prompt's last), so its
            # re-prefill under the block mask gives the K/V the commits
            # gave, and nothing is left over
            whole = len(prompt) - len(prompt) % self.config.model.block_length
            seq.prompt, seq.block_tail = prompt[:whole], prompt[whole:]
            assert not (resume and seq.block_tail), "resumed inside a block"
        seq.resume = resume
        seq.phase = "prefill"
        seq.pages = shared + new_ids
        seq.own_from = len(shared)
        seq.length = 0
        seq.generated = []
        seq.dispatched = 0
        seq.cancelled = False
        seq.prefill_off = len(shared) * ps
        self._prefix_shared_tokens += len(shared) * ps
        seq.dense_caches = None
        seq.last_logits = None
        self._admit_clock += 1
        seq.admit_at = self._admit_clock
        self._by_id[request.request_id] = seq
        self._unpark_note(request)
        reqtrace.record(request.request_id, reqtrace.ADMITTED,
                        shared_pages=len(shared),
                        tail_pages=tail_pages,
                        resume_tokens=len(resume) or None)
        return True

    def _stage_prefill_cache(self, seq: _Seq):
        """The dense chunk cache of a sequence about to prefill, with
        its shared span gathered in so the tail attends over it without
        recomputing."""
        with self._mesh_scope():
            self._dispatching()
            dense = self._dense_zero_caches()
            if seq.own_from:
                pad = np.zeros(self.config.pages_per_seq, np.int32)
                pad[:seq.own_from] = seq.pages[:seq.own_from]
                dense = self._gather_pages(self.k_pages, self.v_pages,
                                           dense, jnp.asarray(pad))
            self._dispatched(jax.tree_util.tree_leaves(dense)[0])
        seq.dense_caches = dense

    def _prefill_tick(self, part=_no_phase):
        """Advance at most `prefill_decode_ratio` prefill chunks,
        round-robin across prefilling sequences in admission order, so
        a long prompt never stalls the decode batch for more than one
        bounded chunk per tick. `part` (`StepTimer.part`) times the two
        halves of the `prefill` phase apart, in the visits that run them:
        a chunk's staging and dispatch (`prefill_chunk_s`), and what a
        finished prompt costs the stepping thread (`prefill_finish_s`:
        the page write, the radix insert, `first_token`)."""
        budget = max(1, self.config.prefill_decode_ratio)
        order = sorted(
            (i for i, s in enumerate(self.seqs)
             if s.request is not None and s.phase == "prefill"
             and not s.cancelled),
            key=lambda i: self.seqs[i].admit_at)
        if not any(s.request is not None and s.phase == "decode"
                   for s in self.seqs):
            # nothing decoding → no decode latency to protect; drain
            # the prefill backlog at full speed (cold-start ramp)
            budget = max(budget, len(order))
        while budget > 0 and order:
            i = order.pop(0)
            seq = self.seqs[i]
            if self._blockwise and seq.prefill_off >= len(seq.prompt):
                # no whole block of the prompt is left to compute (shorter
                # than a block, or every page of it came from the radix)
                with part("prefill", "finish"):
                    self._finish_prefill(i)
                self._prompts_finished += 1
                continue
            if self._windowed and not self._chunk_pages(i, seq):
                budget -= 1
                continue     # parked again: the pool is short
            with part("prefill", "chunk"):
                self._prefill_heads += self._prefill_chunk(seq)
            self._prefill_chunks += 1
            budget -= 1
            if seq.prefill_off >= len(seq.prompt):
                with part("prefill", "finish"):
                    self._finish_prefill(i)
                self._prompts_finished += 1
            else:
                order.append(i)

    def _chunk_size(self, seq: _Seq) -> Tuple[int, int]:
        """(bucket, real tokens) of `seq`'s next prefill chunk."""
        rem = len(seq.prompt) - seq.prefill_off
        chunk = self._bucket(min(rem, self.config.prefill_buckets[-1]))
        return chunk, min(rem, chunk)

    def _chunk_pages(self, index: int, seq: _Seq) -> bool:
        """The pages `seq`'s next chunk writes its real tokens into, for a
        model whose chunks write the row's pages (`_windowed`). Admission
        found the row's budget free, but the rows beside it have grown
        since: where the pool is short now, the row goes back to the front
        of the queue with what it had (False) and is admitted again when
        its budget is free."""
        cfg = self.config
        _, take = self._chunk_size(seq)
        # rows, not positions: a chunk that fills its window is written
        # whole before the window is compressed
        need = -(-(cfg.model.cache_rows(seq.prefill_off) + take)
                 // cfg.page_size)
        while len(seq.pages) < need:
            page = self._alloc_page()
            if page is None:
                self._preempt(index, reason="page_pressure")
                return False
            seq.pages.append(page)
        return True

    def _row_table(self, seq: _Seq):
        """One row's block table: its pages, then the null page."""
        table = np.zeros((self.config.pages_per_seq,), np.int32)
        table[:len(seq.pages)] = seq.pages
        return table

    def _close_window(self, seq: _Seq, where: str):
        """`seq`'s open window is full: `compress_window` turns its pages
        into summaries in every layer, in place in the first of them, and
        the rest go back to the pool. Dispatched in stream order behind
        the step (or chunk) that wrote the window's last position; the
        host knows from the row's length alone that it is due, so nothing
        is read and the step ahead stays ahead. A page released here is
        written by its next owner only in a program dispatched later."""
        cfg = self.config
        model = cfg.model
        full = model.window_size // cfg.page_size
        kept = model.window_summaries // cfg.page_size
        base = len(seq.pages) - full
        assert base >= 0 and base % kept == 0, (base, len(seq.pages))
        window = np.zeros((full,), np.int32)
        window[:] = seq.pages[base:]
        with self._mesh_scope():
            self._dispatching()
            self.k_pages, self.v_pages = self._compress_window(
                self.params, self.k_pages, self.v_pages, window)
            self._dispatched(self.k_pages[0])
        for page in seq.pages[base + kept:]:
            self.pool.decref(page)
        del seq.pages[base + kept:]
        self._window_closes[where] += 1
        self._pages_released += full - kept

    def _prefill_chunk(self, seq: _Seq) -> bool:
        """One bucket-rounded chunk of `seq`'s remaining prompt into its
        dense cache — one compiled shape per bucket, whatever the
        prompt's length. Returns whether the chunk ran the head: only the
        chunk that holds the prompt's final token does, on that row, and
        leaves its logits in `seq.last_logits`."""
        cfg = self.config
        prompt = seq.prompt
        off = seq.prefill_off
        chunk, take = self._chunk_size(seq)
        if self._sorted_buckets and chunk in self._sorted_buckets:
            self._prefill_chunks_sorted += 1
        tokens = np.zeros((1, chunk), np.int32)
        tokens[0, :take] = prompt[off:off + take]
        positions = np.minimum(
            np.arange(off, off + chunk, dtype=np.int32),
            cfg.model.max_seq_len - 1)[None, :]
        trace = not reqtrace.reqtrace_disabled()
        if trace:
            chunk_t0 = time.monotonic()
            compile_t0 = self._compile_total()
        # a scan layer must be told where the bucket's padding starts
        valid = () if self.state is None \
            else (jnp.asarray(take, jnp.int32),)
        # the row the first token is sampled from, if this chunk holds it:
        # the program applies the head to that row and to nothing else
        finishes = off + take == len(prompt)
        last = (jnp.asarray(take - 1 if finishes else -1, jnp.int32),)
        if self._blockwise:
            staged = (self.k_pages, self.v_pages, self.chunk_counters)
            extra = (self._row_table(seq), jnp.asarray(take, jnp.int32))
            self._prefill_ctx_rows += off + take
            self._chunks_counted += chunk == cfg.prefill_buckets[-1]
            last, finishes = (), False       # no head, no logits
        elif self._windowed:
            # chunks start at multiples of the largest bucket, which
            # divides the window: none straddles a close
            window = cfg.model.window_size
            assert off // window == (off + chunk - 1) // window, (off, chunk)
            staged = (self.k_pages, self.v_pages)
            extra = (self._row_table(seq),)
        elif self._in_place:
            staged = self._row_pools
            extra = (self._row_table(seq), jnp.asarray(take, jnp.int32))
            self._prefill_ctx_rows += off + take
        elif self._pooled:
            staged = dict(seq.dense_caches,
                          kv=list(zip(self.k_pages, self.v_pages)))
            extra = (self._row_table(seq),) + valid
            self._prefill_chunks_in_place += 1
            self._prefill_ctx_rows += off + take
        else:
            staged, extra = seq.dense_caches, valid
        with self._mesh_scope():
            self._dispatching()
            logits, staged = self._chunk_prefill(
                self.params, jnp.asarray(tokens), jnp.asarray(positions),
                staged, jnp.asarray(off, jnp.int32), *extra, *last)
            self._dispatched(logits)
        if self._blockwise:
            self.k_pages, self.v_pages, self.chunk_counters = staged
        elif self._windowed:
            self.k_pages, self.v_pages = staged
            if cfg.model.window_closes(off + take):
                self._close_window(seq, "prefill")
        elif self._in_place:
            self._row_pools = staged
        elif self._pooled:
            self.k_pages = [k for k, _ in staged["kv"]]
            self.v_pages = [v for _, v in staged["kv"]]
            seq.dense_caches = dict(staged, kv=[])
        else:
            seq.dense_caches = staged
        if finishes:
            # stays on the device: `first_token` samples from it there
            seq.last_logits = logits
        seq.prefill_off = off + take
        if trace and seq.request is not None:
            # no chunk waits for the device: dur_s is the launch alone
            reqtrace.record(
                seq.request.request_id, reqtrace.PREFILL_CHUNK,
                tokens=take, bucket=chunk,
                valid=take if valid else None,
                dur_s=round(time.monotonic() - chunk_t0, 6),
                compile_s=round(
                    self._compile_total() - compile_t0, 6) or None)
        # counts COMPUTED tokens only — a radix-shared span costs zero
        llm_metrics().prefill_tokens.inc(take, tags=_TAGS)
        self._prefill_computed_tokens += take
        return finishes

    def _write_owned_pages(self, dense_caches, write_ids, start_page):
        """Commit owned prompt pages from a dense prefill cache to the
        pools. The id list is padded to pages_per_seq with the null
        page so `_write_pages` keeps one compiled shape per dense-cache
        length instead of one per tail size."""
        cfg = self.config
        ids = list(write_ids) + [0] * (cfg.pages_per_seq
                                       - len(write_ids))
        with self._mesh_scope():
            self._dispatching()
            self.k_pages, self.v_pages = self._write_pages(
                self.k_pages, self.v_pages, dense_caches,
                jnp.asarray(ids, jnp.int32),
                jnp.asarray(start_page * cfg.page_size, jnp.int32))
            self._dispatched(self.k_pages[0])

    def _finish_prefill(self, index: int):
        """Prompt fully cached: write the owned tail pages, commit full
        pages to the radix, sample the first token from the one row of
        logits the last chunk returned (`seq.last_logits`) into the token
        vector ON THE DEVICE, and move the sequence to the decode phase.
        Nothing here waits for the device: the host
        work (the radix insert above all) runs under the decode step in
        flight, and the token is read with the next visit's."""
        seq = self.seqs[index]
        request = seq.request
        prompt = seq.prompt
        # a `_windowed` or `_in_place` model's chunks wrote the row's pages
        write_ids = [] if self._windowed or self._in_place or self._pooled \
            or self._blockwise else seq.pages[seq.own_from:]
        staged = seq.dense_caches
        if self.state is not None:
            self._state_due.append((index, staged["state"]))
            staged = staged["kv"]
        if write_ids:
            self._write_owned_pages(staged, write_ids, seq.own_from)
        seq.dense_caches = staged = None
        self._register_prefix(prompt, seq.pages)
        if self._blockwise:
            # nothing is sampled from a prompt: the row's first block opens
            # with the next block step
            seq.phase = "decode"
            seq.length = len(prompt)
            seq.generated = []
            seq.dispatched = 0
            seq.block_at = -1
            seq.block_rule = self._block_settings(request)
            return
        temp, top_k, top_p = self._sampling(request)
        key = self._rng
        if temp > 0:
            self._rng, key = jax.random.split(self._rng)
        self._dispatching()
        self._tokens = first_token(
            self._tokens, seq.last_logits, np.int32(index), key,
            np.full((1,), temp, np.float32), np.full((1,), top_k, np.int32),
            np.full((1,), top_p, np.float32), sampled=temp > 0)
        self._dispatched(self._tokens)
        seq.last_logits = None
        self._tokens.copy_to_host_async()
        self._unread.append((index, seq))
        seq.phase = "decode"
        seq.length = len(prompt)
        seq.generated = []
        seq.dispatched = 1

    def _exhausted(self, seq: _Seq) -> bool:
        """Whether the newest token computed or in flight for `seq` is
        its last by the rules that do not look at it: the request's
        budget (tokens from before a preemption count) or the engine's
        length cap. Such a row is in no further decode step. A row of a
        `_blockwise` model: the last denoising forward of its last block
        is dispatched, as the static rule counts (that block's commit would
        be attended by nothing, and is not run)."""
        if self._blockwise:
            return seq.block_at >= 0 and not seq.block_masks \
                and seq.block_last
        return len(seq.resume) + seq.dispatched \
            >= seq.request.max_new_tokens \
            or seq.length >= self.config.max_len - 1

    def _finished_after(self, seq: _Seq, token: int) -> bool:
        """Whether `token`, just emitted, was `seq`'s last: EOS, or the
        last of an exhausted row."""
        eos = self.config.eos_token
        return (eos is not None and token == eos) \
            or (seq.dispatched == len(seq.generated)
                and self._exhausted(seq))

    def _finish(self, index: int):
        seq = self.seqs[index]
        request = seq.request
        tokens = seq.resume + seq.generated
        self._end_request(request, tokens, index=index)
        self._finished.append((request, tokens))

    def _alloc_page(self) -> Optional[int]:
        """Allocate with radix pressure relief: cold unshared prefix
        pages are reclaimed before giving up."""
        page = self.pool.alloc()
        if page is None and self.radix.evict_pages(1):
            self._radix_evictions += 1
            page = self.pool.alloc()
        return page

    def _ensure_decode_pages(self, active: List[int]) -> List[int]:
        """Lazy page growth before the decode tick: every decoding
        sequence needs the page its next token writes into. Under pool
        exhaustion the YOUNGEST sequence is preempted (pages released,
        request parked at the queue front with its generated tokens as
        a prompt extension) until the rest fit — the continuous-batching
        answer to OOM. A preemption needs every token of its row on the
        host, so the step in flight is read first (`_drain`), which may
        itself end rows and free the pages that were short."""
        ps = self.config.page_size
        # the row of its pages a row's next token lands in: its length,
        # unless the model's rows keep something else than their context
        at = self.config.model.cache_rows if self._windowed \
            else (lambda length: length)
        if self._blockwise:
            # the last position of the block a row has open or opens next
            last = self.config.model.block_length - 1
            at = lambda length: length + last       # noqa: E731
        rows = {i: self.seqs[i] for i in active}
        for n, i in enumerate(
                sorted(active, key=lambda i: self.seqs[i].admit_at)):
            if not n & 7:
                self._poll()
            seq = rows[i]
            while self.seqs[i] is seq \
                    and at(seq.length) // ps >= len(seq.pages):
                page = self._alloc_page()
                if page is not None:
                    seq.pages.append(page)
                    continue
                if self._unread:
                    self._drain("preempt")
                    continue
                victim = max((j for j in rows if self.seqs[j] is rows[j]),
                             key=lambda j: rows[j].admit_at)
                self._preempt(victim, reason="page_pressure")
        return [i for i in active if self.seqs[i] is rows[i]]

    def _preempt(self, index: int, reason: str):
        self._drain("preempt")
        seq = self.seqs[index]
        request = seq.request
        if request is None:
            return   # ended by a token the drain read
        # generated-so-far becomes a prompt extension; re-admission
        # radix-matches the already-registered prompt pages, so only
        # the generated span (plus the partial page) re-prefills (all of
        # it for a model with recurrent state, which registers none)
        request._resume_tokens = seq.resume + list(seq.generated)
        self._release(seq)
        self.seqs[index] = _Seq()
        reqtrace.record(request.request_id, reqtrace.PREEMPTED,
                        reason=reason,
                        generated=len(request._resume_tokens))
        self._park_note(request, reason)
        self._parked.appendleft(request)
        self._preemptions += 1
        llm_metrics().preemptions.inc(tags=dict(_TAGS, reason=reason))

    def _bucket(self, n: int) -> int:
        for b in self.config.prefill_buckets:
            if n <= b:
                return b
        return self.config.prefill_buckets[-1]

    def prefill_only(self, prompt: List[int]):
        """Run chunked prefill WITHOUT admitting a sequence: returns
        (last_token_logits, per-layer dense (k, v) numpy pairs) trimmed to
        whole pages. This is the prefill half of prefill/decode
        disaggregation (reference:
        llm/_internal/serve/deployments/prefill_decode_disagg/) — the KV
        ships to a decode engine's `submit_prefilled`."""
        self._no_recurrent("prefill_only")
        seq = _Seq(prompt=list(prompt))
        self._stage_prefill_cache(seq)
        while seq.prefill_off < len(seq.prompt):
            self._prefill_chunk(seq)
        n_tok = -(-len(prompt) // self.config.page_size) * \
            self.config.page_size
        out = [(np.asarray(k[:, :, :n_tok]), np.asarray(v[:, :, :n_tok]))
               for (k, v) in seq.dense_caches]
        return np.asarray(seq.last_logits[0], np.float64), out

    def _match_prefix(self, prompt: List[int]) -> List[int]:
        """Longest cached full-page prefix of `prompt`: refcounted page
        ids the caller maps copy-on-write into its block table. Pages
        carry no recurrent state, so a model that has it matches nothing
        (and `_register_prefix` registers nothing); nor does one whose
        pages stop being a prefix's K/V when their window closes."""
        if self.state is not None:
            self._prefix_skipped_recurrent += 1
            return []
        if self._windowed:
            self._prefix_skipped_compressed += 1
            return []
        shared = self.radix.match(prompt)
        if shared:
            self._prefix_hits += 1
            llm_metrics().prefix_hits.inc(tags=_TAGS)
        elif len(prompt) >= self.config.page_size:
            self._prefix_misses += 1
            llm_metrics().prefix_misses.inc(tags=_TAGS)
        return shared

    def _register_prefix(self, prompt: List[int], pages: List[int]):
        """Commit the full prompt pages for reuse; the radix enforces
        the entry budget (`RTPU_PREFIX_CACHE_ENTRIES`)."""
        if self.state is not None or self._windowed:
            return
        n_full = len(prompt) // self.config.page_size
        # re-read the flag so tests / live reconfig take effect
        self.radix.max_entries = int(CONFIG.prefix_cache_entries)
        if n_full:
            before = self.radix.entries
            added = self.radix.insert(prompt, pages[:n_full])
            self._radix_evictions += before + added - self.radix.entries
        llm_metrics().prefix_entries.set(self.radix.entries,
                                         tags=_GAUGE_TAGS)

    def _evict_prefixes(self, max_entries: int):
        self.radix.evict(max_entries)
        llm_metrics().prefix_entries.set(self.radix.entries,
                                         tags=_GAUGE_TAGS)

    def prefix_pinned_pages(self) -> set:
        """Distinct physical pages the radix holds a reference on."""
        return set(self.radix.pages())

    def page_leak_check(self) -> int:
        """Pool-balance audit: recompute every page's expected refcount
        from live sequences plus the prefix store and compare against
        the allocator. Returns the number of inconsistent pages (0 =
        balanced); call between steps — completions, cancels, preempts
        and evictions must all keep this at zero."""
        expected = np.zeros(self.config.num_pages, np.int64)
        expected[0] = 1  # the null pad page
        for seq in self.seqs:
            for page in seq.pages:
                expected[page] += 1
        for page in self.radix.pages():
            expected[page] += 1
        bad = int(np.sum(expected != self.pool.refs))
        # the free list must hold exactly the zero-ref pages
        if len(self.pool._free) != int(np.sum(self.pool.refs[1:] == 0)):
            bad += 1
        return bad

    def autoscaling_metrics(self) -> Dict[str, Any]:
        """Signals for the serve autoscaler's closed loop (the replica's
        get_metrics() forwards them to the controller): waiting work,
        recent median TTFT, and KV page occupancy."""
        ttfts = sorted(self._recent_ttfts)
        usable = max(1, self.config.num_pages - 1)
        out: Dict[str, Any] = {
            "queued": self._waiting_count(),
            "kv_occupancy": 1.0 - self.pool.num_free() / usable,
        }
        if ttfts:
            out["ttft_s"] = ttfts[len(ttfts) // 2]
        return out

    def _sampling(self, request: GenerationRequest):
        """(temperature, top_k, top_p) as the sampler takes them: 0
        disables the k filter, 1.0 the nucleus."""
        temp = request.temperature
        top_p = getattr(request, "top_p", None)
        return (temp if temp is not None else self.config.temperature,
                getattr(request, "top_k", None) or 0,
                top_p if top_p is not None else 1.0)

    def _drain(self, reason: str, phase=_no_phase):
        """Read the step in flight with nothing dispatched behind it: the
        exception path, taken where the host needs a token's value (or
        has no row to dispatch) before it can go on. Counted by reason."""
        if not self._unread:
            return
        self._drained_ticks[reason] = \
            self._drained_ticks.get(reason, 0) + 1
        unread, self._unread = self._unread, []
        with phase("wait"), _accel.pause("drain/" + reason):
            values = self._fetch(self._tokens)
            if self._dry is not None:
                self._dry.waited(self._tokens)
        with phase("emit"):
            self._emit_tokens(unread, values)

    def _fetch(self, tokens) -> List[int]:
        """The token vector on the host: the one wait for the device a
        visit has (for the step dispatched a visit earlier, and for the
        prefill chunks behind it whose first tokens the vector holds)."""
        # behind the next step's dispatch wherever a row decodes again
        return np.asarray(tokens).tolist()  # host-sync ok: the one fetch

    def _emit_tokens(self, unread: List[Tuple[int, _Seq]],
                     values: List[int]):
        """Hand the tokens just read to their rows, in slot order within
        a step and a finished prefill's after them; end the rows they
        finish. A row that ended since its entry was made is skipped (its
        token was counted as discarded when it was released), a row
        cancelled since is released here and its token dropped."""
        if self._blockwise:
            return self._emit_blocks(unread, values)
        for slot, seq in unread:
            if self.seqs[slot] is not seq:
                continue
            if seq.cancelled:
                self._end_request(seq.request, None, index=slot,
                                  where="decode")
                continue
            token = values[slot]
            seq.generated.append(token)
            self._tokens_generated += 1
            if len(seq.generated) == 1:
                self._note_first_token(seq)
            callback = getattr(seq.request, "_token_callback", None)
            if callback is not None:
                callback(seq.request, token)
            if self._finished_after(seq, token):
                self._finish(slot)

    def _note_first_token(self, seq: _Seq):
        request = seq.request
        submit_ts = getattr(request, "_submit_ts", None)
        park_s = getattr(request, "_rt_park_total", 0.0)
        if submit_ts is not None and not seq.resume:
            ttft = time.monotonic() - submit_ts
            llm_metrics().ttft.observe(ttft, tags=_TAGS)
            self._recent_ttfts.append(ttft)
            # the DECODE stamp splits a parked request's TTFT: park_s
            # is the admission-blocked share, the rest is real prefill
            reqtrace.record(request.request_id, reqtrace.DECODE,
                            ttft_s=round(ttft, 6),
                            park_s=round(park_s, 6) or None)
        else:
            reqtrace.record(request.request_id, reqtrace.DECODE,
                            resumed=True,
                            park_s=round(park_s, 6) or None)

    def _release(self, seq: _Seq):
        for page in seq.pages:
            self.pool.decref(page)
        self._by_id.pop(seq.request.request_id, None)

    def _decode_tick(self, phase):  # rtpu: hot-loop
        """The decode half of a visit: dispatch the next step for every
        row that will decode again, THEN read the tokens of the step
        dispatched a visit earlier. `phase` is the tick's
        `StepTimer.phase`. What the step is told about its rows is kept
        between visits (`staging.StagedRows`): `sync` writes the rows that
        joined, left or took a page, the accounts of what the step attends
        are sums over the kept lengths and page counts, and of the arrays
        only `lengths` is sent every visit."""
        tick_start = time.monotonic()
        cfg = self.config
        with phase("grow"):
            # a row whose token in flight is its last by rule, or that was
            # cancelled, is in no further step; lazy page growth for the
            # rest (+ preemption under pressure)
            active = self._ensure_decode_pages([
                i for i, s in enumerate(self.seqs)
                if s.request is not None and s.phase == "decode"
                and not s.cancelled and not self._exhausted(s)])
        if not active:
            self._drain("idle", phase)
            return
        with phase("stage"):
            trace = not reqtrace.reqtrace_disabled()
            if trace:
                # snapshot ids now: finished slots are reset before the
                # compile delta is attributed below
                trace_rids = [self.seqs[i].request.request_id
                              for i in active]
                compile_t0 = self._compile_total()
            stage, poll = self._stage, self._poll
            poll()
            stage.sync(active, self.seqs, self._sampling)
            poll()
            index = stage.index
            lengths = stage.lengths[index]
            if self._windowed:
                summary, window = cfg.model.attended_rows(lengths)
                self._summary_rows += int(summary.sum())  # host-sync ok: numpy
                self._window_rows += int(window.sum())  # host-sync ok: numpy
            if self._in_place:
                # the cached rows the step attends or scores, its own
                # token's among them, and the pages they lie in, a row
                rows = len(active) \
                    + int(lengths.sum())  # host-sync ok: numpy
                pages = int(stage.held[index].sum())  # host-sync ok: numpy
            if self._latent:
                self._latent_rows_attended += rows
                self._latent_pages_rowwise += pages
                self._latent_pages_copied += pages
            if self._indexed:
                self._index_rows_scanned += rows
                self._index_pages_rowwise += pages
                self._sparse_rows_context += rows
                self._sparse_rows_selected += int(  # host-sync ok: numpy
                    np.minimum(lengths + 1, cfg.model.index_topk).sum())
            seqs = self.seqs
            for i in active:
                # this step's token, in flight from here on
                seq = seqs[i]
                seq.length += 1
                seq.dispatched += 1
            if self._in_place:
                # the pages those rows hold, each once
                seen = self._page_seen
                seen[stage.tables[index].ravel()] = True
                seen[0] = False
                if self._latent:
                    self._latent_pages_distinct += np.count_nonzero(seen)
                else:
                    self._index_pages_distinct += np.count_nonzero(seen)
                seen[:] = False
            poll()
            if self._latent:
                # and what the kernel does not copy of them: a group's
                # shared span for every member but one, by the schedule
                # the device makes of the same arrays
                self._latent_pages_copied -= int(  # host-sync ok: numpy
                    pages_spared(share_schedule(
                        stage.tables, stage.lengths, cfg.page_size)))
                poll()
            self._decode_rows += len(active)
            self._sampler_steps[stage.tier] += 1
            poll()
            self._rng, key = jax.random.split(self._rng)
        accel = self._accel
        timer = accel.StepTimer(
            "decode", tokens=len(active),
            flops=2.0 * self._num_params * len(active),
            sink=self._step_accum) \
            if accel is not None else None
        with timer if timer is not None else contextlib.nullcontext():
            with self._mesh_scope():
                with (timer.device() if timer is not None
                      else contextlib.nullcontext()):
                    with phase("stage"):
                        def send(name):
                            poll()
                            return stage.send(name)
                        args = (send("tables"), send("lengths"),
                                self._tokens, key, send("temps"),
                                send("top_ks"), send("top_ps"))
                        # the rows that decode, for the layers that count
                        # or scan
                        live = (send("live"),) if self._in_place \
                            or self.state is not None else ()
                    with phase("dispatch"):
                        unread, tokens = self._unread, self._tokens
                        self._dispatching()
                        if self._in_place:
                            (self._tokens, self._row_pools,
                             self.counters) = self._decode(
                                self.params, self._row_pools, *live, *args,
                                self.counters)
                        elif self.state is None:
                            (self._tokens, self.k_pages,
                             self.v_pages) = self._decode(
                                self.params, self.k_pages, self.v_pages,
                                *args)
                        else:
                            (self._tokens, self.k_pages, self.v_pages,
                             self.state, self.counters) = self._decode(
                                self.params, self.k_pages, self.v_pages,
                                self.state, *live, *args, self.counters)
                        self._dispatched(self._tokens)
                        # the copy to the host starts when the step ends,
                        # whatever is queued behind it by then
                        self._tokens.copy_to_host_async()
                        stage.sent(advance=True)
                        self._unread = [(i, self.seqs[i]) for i in active]
                        # freed here, inside a phase, not after the last
                        del args, live
                    if self._windowed:
                        with phase("compress"):
                            for i in active:
                                if cfg.model.window_closes(
                                        self.seqs[i].length):
                                    self._close_window(self.seqs[i],
                                                       "decode")
                                    stage.stale(i)
                    if unread:
                        self._lookahead_ticks += 1
                        with phase("wait"):
                            values = self._fetch(tokens)
            if unread:
                with phase("emit"):
                    self._emit_tokens(unread, values)
            with phase("gauges"):
                if trace:
                    compile_s = self._compile_total() - compile_t0
                    if compile_s > 1e-6:
                        # every dispatched request's wall clock contained
                        # the stall — charge it to each (why_slow's
                        # compile bucket, subtracted from its decode
                        # span)
                        for rid in trace_rids:
                            reqtrace.record(
                                rid, reqtrace.COMPILE,
                                compile_s=round(compile_s, 6),
                                phase="decode")
                metrics = llm_metrics()
                metrics.token_latency.observe(
                    time.monotonic() - tick_start, tags=_TAGS)
                metrics.decode_tokens.inc(len(active), tags=_TAGS)

    # -- generation by diffusion over blocks (`_blockwise`) ----------------

    def _block_settings(self, request: GenerationRequest):
        """(denoising forwards a block, the dynamic rule's threshold or a
        number no probability passes under the static rule) of a request,
        the model configuration's where it does not say."""
        model = self.config.model
        steps = getattr(request, "denoising_steps", None) \
            or model.denoising_steps
        rule = getattr(request, "remasking", None) or model.remasking
        if rule not in ("static", "dynamic"):
            raise ValueError(f"remasking rule {rule!r} is neither 'static' "
                             f"nor 'dynamic'")
        threshold = getattr(request, "confidence_threshold", None)
        if threshold is None:
            threshold = model.confidence_threshold
        return int(steps), (float(threshold) if rule == "dynamic" else 2.0)

    def _masks_in_flight(self) -> int:
        """Masks the open blocks hold, as the static rule counts them."""
        return sum(s.block_masks for s in self.seqs
                   if s.request is not None and s.block_at >= 0)

    def _plan_forward(self, seq: _Seq):
        """What the next block step does for `seq`, from what the host
        knows without reading anything: (the ids that open a block or
        None, the positions the static rule fixes in this forward; 0 for a
        commit). The static rule's yield is known here; the dynamic rule's
        is at least that, and `_emit_blocks` learns the rest a visit late."""
        model = self.config.model
        L = model.block_length
        fresh = None
        if seq.block_at < 0:
            fixed = seq.block_tail
            seq.block_tail = []
            seq.block_at, seq.block_fixed = seq.length, len(fixed)
            seq.block_masks, seq.block_step = L - len(fixed), 0
            budget = seq.request.max_new_tokens - len(seq.resume) \
                - seq.dispatched
            seq.block_take = min(seq.block_masks, budget)
            seq.block_last = seq.block_take >= budget \
                or seq.block_at + 2 * L > self.config.max_len
            seq.block_counted = False
            seq.block_opened_ts = time.monotonic()
            fresh = fixed + [model.mask_token_id] * seq.block_masks
            if not seq.blocks_done:
                reqtrace.record(seq.request.request_id, reqtrace.BLOCK,
                                what="open", at=seq.block_at,
                                fixed=len(fixed))
        commit = not seq.block_masks
        count = 0
        if commit:
            if seq.length == len(seq.prompt):
                reqtrace.record(seq.request.request_id, reqtrace.BLOCK,
                                what="commit", at=seq.block_at)
            seq.length += L
            self._commit_forwards += 1
        else:
            count = min(unmask_count(L, seq.block_rule[0], seq.block_step),
                        seq.block_masks)
            seq.block_masks -= count
            seq.block_step += 1
            if not seq.block_masks:
                seq.dispatched += seq.block_take
                seq.block_counted = True
        seq.flight.append((seq.block_at, seq.block_fixed, seq.block_take,
                           seq.block_last, seq.block_masks))
        if commit:
            seq.block_at = -1
        return fresh, count

    def _block_tick(self, phase):  # rtpu: hot-loop
        """`_decode_tick` for a model that generates by diffusion over
        blocks: dispatch the next forward of every live row's block (a
        denoising forward, or the commit of a block whose last mask is
        gone, or the first forward of the block a row opens), THEN read the
        report of the step dispatched a visit earlier and hand out the
        tokens of the blocks it finished."""
        tick_start = time.monotonic()
        cfg = self.config
        B, L = cfg.max_batch, cfg.model.block_length
        with phase("grow"):
            active = self._ensure_decode_pages([
                i for i, s in enumerate(self.seqs)
                if s.request is not None and s.phase == "decode"
                and not s.cancelled and not self._exhausted(s)])
        if not active:
            self._drain("idle", phase)
            return
        with phase("stage"):
            trace = not reqtrace.reqtrace_disabled()
            if trace:
                trace_rids = [self.seqs[i].request.request_id
                              for i in active]
                compile_t0 = self._compile_total()
            stage, poll = self._stage, self._poll
            poll()
            # tables, triples and the rows that decode are kept between
            # visits as `_decode_tick`'s are; `lengths` is written below
            stage.sync(active, self.seqs, self._sampling)
            # what a block step takes besides, anew every forward
            lengths = stage.lengths
            opened = np.zeros((B,), bool)
            fresh = np.zeros((B, L), np.int32)
            counts = np.zeros((B,), np.int32)
            thresholds = np.full((B,), 2.0, np.float32)
            for n, i in enumerate(active):
                if not n & 7:
                    poll()
                seq = self.seqs[i]
                thresholds[i] = seq.block_rule[1]
                ids, counts[i] = self._plan_forward(seq)
                if ids is not None:
                    opened[i], fresh[i] = True, ids
                # where this forward's K/V rows go: the block's positions
                lengths[i] = seq.flight[-1][0]
            self._decode_rows += len(active)
            self._block_forwards += len(active)
            self._sampler_steps[stage.tier] += 1
            poll()
            self._rng, key = jax.random.split(self._rng)
        accel = self._accel
        timer = accel.StepTimer(
            "decode", tokens=L * len(active),
            flops=2.0 * self._active_params * L * len(active),
            sink=self._step_accum) \
            if accel is not None else None
        with timer if timer is not None else contextlib.nullcontext():
            with self._mesh_scope(), (timer.device() if timer is not None
                                      else contextlib.nullcontext()):
                with phase("stage"):
                    def send(name):
                        poll()
                        return stage.send(name)

                    def upload(array):
                        poll()
                        return jnp.asarray(array)
                    args = (send("live"), send("tables"), send("lengths"),
                            self._tokens, upload(opened), upload(fresh),
                            upload(counts), upload(thresholds), key,
                            send("temps"), send("top_ks"), send("top_ps"))
                with phase("dispatch"):
                    unread, report = self._unread, self._tokens
                    self._dispatching()
                    (self._tokens, self.k_pages, self.v_pages,
                     self.counters) = self._decode(
                        self.params, self.k_pages, self.v_pages, *args,
                        self.counters)
                    self._dispatched(self._tokens)
                    self._tokens.copy_to_host_async()
                    stage.sent(advance=False)
                    self._unread = [(i, self.seqs[i]) for i in active]
                    del args
                if unread:
                    self._lookahead_ticks += 1
                    with phase("wait"):
                        values = self._fetch(report)
            if unread:
                with phase("emit"):
                    self._emit_blocks(unread, values)
            with phase("gauges"):
                if trace:
                    compile_s = self._compile_total() - compile_t0
                    if compile_s > 1e-6:
                        for rid in trace_rids:
                            reqtrace.record(
                                rid, reqtrace.COMPILE,
                                compile_s=round(compile_s, 6),
                                phase="decode")
                metrics = llm_metrics()
                metrics.token_latency.observe(
                    time.monotonic() - tick_start, tags=_TAGS)
                forwards, commits = self._block_metered
                self._block_metered = (self._block_forwards,
                                       self._commit_forwards)
                commits = self._commit_forwards - commits
                metrics.block_forwards.inc(
                    commits, tags=dict(_TAGS, kind="commit"))
                metrics.block_forwards.inc(
                    self._block_forwards - forwards - commits,
                    tags=dict(_TAGS, kind="denoise"))

    def _emit_blocks(self, unread: List[Tuple[int, _Seq]], values):
        """`_emit_tokens` for a `_blockwise` model: `values` is a block
        step's report on the host, a row a slot (`block_step`). A row's
        forward that found masks and left none finished its block: the
        block's tokens are handed out in position order (the prompt's tail
        and what lies past the request's budget left out), and the row ends
        if the block was its last or held the EOS. Where the dynamic rule
        finished a block ahead of the static count, the forward dispatched
        behind it found no mask and WAS the block's commit: the row's
        account is set right here, a visit late, and no forward is spent
        twice."""
        L = self.config.model.block_length
        metrics = llm_metrics()
        for slot, seq in unread:
            if self.seqs[slot] is not seq:
                continue
            if seq.cancelled:
                self._end_request(seq.request, None, index=slot,
                                  where="decode")
                continue
            at, fixed, take, last, believed = seq.flight.popleft()
            row = values[slot]
            if not row[L] or row[L + 1]:
                continue      # a commit, or a block that still holds masks
            if believed:
                self._block_done_early(seq, at, take, last)
            seq.blocks_done += 1
            if seq.blocks_done == 1:
                reqtrace.record(seq.request.request_id, reqtrace.BLOCK,
                                what="done", at=at, tokens=take,
                                forwards=seq.block_step, open_s=round(
                                    time.monotonic() - seq.block_opened_ts,
                                    6))
            handed = len(seq.generated)
            callback = getattr(seq.request, "_token_callback", None)
            ended = last
            for token in row[fixed:fixed + take]:
                seq.generated.append(token)
                if not handed and len(seq.generated) == 1:
                    self._note_first_token(seq)
                if callback is not None:
                    callback(seq.request, token)
                if token == self.config.eos_token:
                    ended = True
                    break
            handed = len(seq.generated) - handed
            self._tokens_generated += handed
            self._block_tokens_out += handed
            metrics.decode_tokens.inc(handed, tags=_TAGS)
            metrics.block_tokens_out.inc(handed, tags=_TAGS)
            if ended:
                reqtrace.record(seq.request.request_id, reqtrace.BLOCK,
                                what="last", at=at, blocks=seq.blocks_done)
                self._finish(slot)

    def _block_done_early(self, seq: _Seq, at: int, take: int, last: bool):
        """The dynamic rule emptied the block at `at` in a forward after
        which the static count still had masks in it."""
        self._blocks_early += 1
        if seq.block_at != at:
            return
        if not seq.block_counted:
            seq.dispatched += take
            seq.block_counted = True
        seq.block_masks = 0
        if seq.flight and not last:
            # the forward dispatched behind it found no mask: the commit
            seq.length += self.config.model.block_length
            seq.block_at = -1
            self._commit_forwards += 1

    # -- conveniences ------------------------------------------------------

    def generate(self, prompts: List[List[int]],
                 max_new_tokens: int = 32,
                 timeout_s: float = 300.0) -> List[List[int]]:
        results: Dict[int, List[int]] = {}
        for i, prompt in enumerate(prompts):
            self.submit(GenerationRequest(
                prompt_tokens=prompt, max_new_tokens=max_new_tokens,
                request_id=str(i)))
        deadline = time.monotonic() + timeout_s
        while len(results) < len(prompts):
            if time.monotonic() > deadline:
                raise TimeoutError("generation timed out")
            for request, tokens in self.step():
                results[int(request.request_id)] = tokens
        return [results[i] for i in range(len(prompts))]

    def stats(self) -> Dict[str, Any]:
        """The engine's running sums and sizes. Beside the keys of every
        model, those of `_ahead_counts` for the model's contract; an
        `_indexed` model's are `index_rows_scanned` (cached index keys the
        decode steps scored, a row a step), `index_pages_rowwise` /
        `index_pages_distinct` (pages of them counted a row / once a
        step), `sparse_rows_selected` (sum of min(context, topk)) and
        `sparse_rows_context` (sum of the contexts), `prefix_shared_tokens`,
        `prefill_computed_tokens`, `prefill_ctx_rows`, `radix_evictions`,
        `radix_evict_walks` (walks of the whole radix that eviction made:
        at most one a call that had to drop a node, `radix.py`),
        and `index_cache_bytes` / `sparse_kernel`. `stage_steps` /
        `stage_uploads`: steps dispatched and the kept arrays sent for them
        (`staging.StagedRows`; their quotient is the uploads a step, of the
        five arrays, or six, that a step takes)."""
        self._flush_step_rows()  # surfaces the partial window
        index_bytes = sum(int(np.prod(pool.shape)) * pool.dtype.itemsize
                          for pool in self.index_pages)
        cache_bytes = index_bytes + sum(
            int(np.prod(pool.shape)) * pool.dtype.itemsize
            for pool in self.k_pages + self.v_pages)
        kinds = _layer_caches(self.config.model)
        # the copy `read_counters` last published; where steps have run
        # since, the stepping thread is asked for a newer one and makes it
        # before its next step (nothing here touches the donated arrays)
        counters = self._counters_host
        self._counters_asked = bool(counters) \
            and self._counters_at != self._steps
        param_bytes = sum(
            int(np.prod(p.shape)) * p.dtype.itemsize
            for p in jax.tree_util.tree_leaves(self.params))
        return {
            "steps": self._steps,
            "tokens_generated": self._tokens_generated,
            "active": sum(1 for s in self.seqs if s.request is not None),
            "pending": self._waiting_count(),
            "free_pages": self.pool.num_free(),
            "prefix_entries": self.radix.entries,
            "prefix_hits": self._prefix_hits,
            "prefix_misses": self._prefix_misses,
            "preemptions": self._preemptions,
            # the step ahead (`_ahead_counts`; `drained_by`: why)
            **self._ahead_counts(),
            "drained_by": dict(self._drained_ticks),
            # the device out of work (`accel.DryWatch`): programs
            # dispatched, those that found it dry, the seconds it was
            # (`dry_s`, the mean of the two bounds) and under which phase
            "dry": dict(self._dry.totals,
                        by_phase=dict(self._dry.totals["by_phase"]))
            if self._dry is not None else {},
            "sampler": dict(zip(SAMPLER_TIERS, self._sampler_steps)),
            # recurrent state beside the pages (zeros for a model
            # without it)
            "state_bytes": sum(
                a.size * a.dtype.itemsize
                for a in jax.tree_util.tree_leaves(self.state)),
            "state_installs": self._state_installs,
            "prefix_skipped_recurrent": self._prefix_skipped_recurrent,
            # what each layer keeps: p(ages), s(tate), c(ounters); and,
            # per layer that counts, per expert held: tokens routed to it
            # and decode steps that routed it any, as of the last
            # `read_counters`
            "layer_kinds": ["".join(tag for tag, kept in zip("psc", kind)
                                    if kept) or "-" for kind in kinds],
            "expert_pairs": [pairs.tolist() for pairs, _ in counters],
            "expert_steps": [steps.tolist() for _, steps in counters],
            # the same of a `_blockwise` model's prefill chunks of the
            # largest bucket (`prefill_chunks_largest` of them)
            "chunk_expert_pairs": [pairs.tolist() for pairs, _
                                   in self._chunk_counters_host],
            "chunk_expert_steps": [steps.tolist() for _, steps
                                   in self._chunk_counters_host],
            # pool-balance audit; exact only between steps
            "leaked_pages": self.page_leak_check(),
            # "pallas" or "gather": the path `decode_step` holds, of
            # ops.latent_attention for a model whose layers cache latent
            # rows, else of ops.paged_attention
            ("latent_kernel" if self._latent else "sparse_kernel"
             if self._indexed else "paged_kernel"): self._paged_kernel,
            "index_cache_bytes": index_bytes,
            "tp": self._tp,
            "hbm_cache_bytes": cache_bytes,
            # per-chip residency: pages shard on kv_heads, params on
            # their logical axes — both divide by the tensor degree (the
            # fsdp/replicated leaves make this a ceiling for params)
            "hbm_cache_bytes_per_device": cache_bytes // self._tp,
            "hbm_param_bytes": param_bytes,
            "hbm_param_bytes_per_device": self._param_bytes_per_device(),
        }

    def _param_bytes_per_device(self) -> int:
        """Actual per-device parameter residency: sums each leaf's
        addressable shard size on device 0 (exact, not estimated)."""
        total = 0
        for p in jax.tree_util.tree_leaves(self.params):
            if hasattr(p, "sharding") and hasattr(p, "addressable_shards"):
                shard = p.addressable_shards[0]
                total += int(np.prod(shard.data.shape)) * p.dtype.itemsize
            else:
                total += int(np.prod(p.shape)) * p.dtype.itemsize
        return total
