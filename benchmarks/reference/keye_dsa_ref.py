"""Plain reference for the language model of Keye-VL-2.0-30B-A3B
(`model_type: KeyeVL2`): a grouped-query decoder whose every query attends
only the `sa_config.topk` cached tokens an indexer scores highest, beside
softmax-routed SwiGLU experts. Written from the published config.json's keys
and the family's description (DeepSeek-Sparse-Attention's lightning indexer),
independent of ray_tpu.models and ray_tpu.ops: no cache, no kernels, no
batching. u = n(x) is a layer's input after its RMSNorm (eps `rms_norm_eps`),
t, s positions:

  attention                       H = num_attention_heads, G = num_key_value_heads
     q_h = R_t n'(Wq_h . u)  (head_dim) ;  k_g = R_t n'(Wk_g . u) ;  v_g = Wv_g . u
                                  n': RMSNorm over a head's lanes (assumed)
     qI_j = R'_t (WqI_j . u)  (indexer_num_heads x indexer_head_dim)
     kI   = R'_t (WkI . u)    (ONE key a token: indexer_num_kv_heads = 1)
     w_j  = (Ww . u)_j
     I(t, s) = c sum_j w_j(t) relu(qI_j(t) . kI(s)),  s <= t
               c = indexer_num_heads^-1/2 indexer_head_dim^-1/2 (assumed)
     S_t = the `topk` positions s <= t of largest I(t, s), ties to the
           earlier position; all of them while t < topk
     o_h(t) = sum_{s in S_t} softmax_{s in S_t}(q_h(t) . k_g(h)(s) / head_dim^1/2) v_g(h)(s)
     x <- x + Wo . [o_h]
  R (and R' over the index lanes): rotary at rope_theta, pair j = lanes (j,
  j + d/2), angle t rope_theta^(-2j/d). `rope_scaling.mrope_section` gives
  pair j one of three position streams (temporal, height, width); a text
  token carries its position in all three, and `mrope_angles` with three
  equal streams IS `rotary_angles` (a test holds them together).
  experts, every layer
     p = softmax(Wr . u) in R^E, float32           E = the router's width
     chosen = the k largest p ;  w_e = p_e / sum_chosen p   (norm_topk_prob)
     E_e(u) = Wd_e . (silu(Wg_e . u) * Wu_e . u)   moe_intermediate_size wide
     x <- x + sum_chosen w_e E_e(u)                no shared expert
  x = embed[tokens]; logits = lm_head . n(x)       untied

Given `held_experts` = (first, count), the sum over the chosen experts runs
over the held ones only, as the program under test leaves the others out.
The router is never cut.

float32 throughout under jax.default_matmul_precision("highest").
Everything that acts on a position alone is computed a block of positions at
a time, and the attention a block of queries at a time (the scores, the
selection and the softmax of QUERY_BLOCK queries against every position),
only so that a long document fits beside a serving engine.

Several sequences that begin with the same tokens may be given as ONE
(`branch`, `positions`), as benchmarks/reference/sarvam_mla_ref.py takes
them: token t's candidates are the tokens no later in the array that are of
the trunk (branch 0) or of t's own branch, which is each sequence's own
causal order.

Selection and routing are discontinuous, so the reference can be told what
the program under test chose and follow it: `routes` (the experts, per
layer), and `selection` (per layer, for EVERY token, the indices in the
array of the tokens that the program attended): attention and logits are
then held to the reference apart from the selection, and the reference's
own scores and selection come back beside (`details`) to be compared with
the program's.

Assumed (the configuration file says the same); each names its one place:
  - a per-head RMSNorm on q and k before the rotary map: `_project`.
  - the rotary map on qI and kI over all indexer_head_dim lanes at the same
    theta and the text position, no norm on kI: `_project`.
  - the score's scale c, a positive constant that changes no selection:
    `_index_scores`.
  - ties broken towards the earlier position: `_select`.
  - q_chunk_size / kv_chunk_size are the published code's tile, not a
    granularity of the selection (topk counts tokens): `_select`.
  - rotary pairs (j, j + d/2): `_rotate`.
  - softmax over all experts, then top-k, then renormalised: `_route`.
Departures: the weights are random (from the seed); the vision tower is not
built (the published config holds the language model only), ids are text.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
EXPERT_BLOCK = 4        # float32 experts at once: 3 x 2048 x 768 x 4 B each
VOCAB_BLOCK = 8192
QUERY_BLOCK = 64        # queries whose scores against every key stand at once
ROW_BLOCK = 1024        # positions a position-wise part takes at once


class Shape(NamedTuple):
    """The published keys the layers need, hashable (a jit static)."""
    heads: int
    kv_heads: int
    head_dim: int
    index_heads: int
    index_dim: int
    topk: int
    eps: float
    theta: float
    experts_per_token: int
    held: Tuple[int, int]


def shape_of(config: Dict[str, Any]) -> Shape:
    """From a config file's keys (the published names) plus
    `held_experts`; without it every routed expert is held."""
    sa = config["sa_config"]
    if sa["indexer_num_kv_heads"] != 1:
        raise ValueError("one index key a token is what is written here")
    return Shape(
        heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        index_heads=sa["indexer_num_heads"], index_dim=sa["indexer_head_dim"],
        topk=sa["topk"], eps=float(config["rms_norm_eps"]),
        theta=float(config["rope_theta"]),
        experts_per_token=config["num_experts_per_tok"],
        held=tuple(config.get("held_experts") or (0, config["num_experts"])))


def _norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(F32)


def rotary_angles(theta: float, dim: int, positions):
    """[s, dim / 2]: position t times theta^(-2j/dim)."""
    return jnp.asarray(positions, F32)[:, None] \
        * theta ** (-jnp.arange(0, dim, 2, dtype=F32) / dim)


def mrope_angles(theta: float, dim: int, sections, positions3):
    """`rope_scaling.mrope_section`: pair j of the dim / 2 takes its
    position from stream 0 (temporal) for the first sections[0] pairs, 1
    (height) for the next sections[1], 2 (width) for the rest. positions3
    [3, s]."""
    stream = jnp.repeat(jnp.arange(3), jnp.asarray(sections),
                        total_repeat_length=dim // 2)
    at = jnp.asarray(positions3, F32)[stream].T             # [s, dim / 2]
    return at * theta ** (-jnp.arange(0, dim, 2, dtype=F32) / dim)


def _rotate(x, angles):
    """x [s, heads, d] by the angles [s, d / 2], halves paired."""
    cos, sin = jnp.cos(angles)[:, None], jnp.sin(angles)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _row_blocks(part, *per_row):
    """`part(*per_row)` -> a tuple of arrays, a block of positions at a
    time (it acts on each position alone), the blocks' results joined."""
    size = next(a.shape[0] for a in per_row if a is not None)
    parts = []
    for at in range(0, size, ROW_BLOCK):
        parts.append(part(*(None if a is None else a[at:at + ROW_BLOCK]
                            for a in per_row)))
        jax.block_until_ready(parts[-1])
    return tuple(jnp.concatenate(column) for column in zip(*parts))


@functools.partial(jax.jit, static_argnames=("sh",))
def _project(x, p, positions, *, sh: Shape):
    """A block of positions' q, k, v, qI, kI, w."""
    with jax.default_matmul_precision("highest"):
        u = _norm(x, p["attn_norm"]["scale"], sh.eps)
        a = p["attn"]
        w_of = lambda name: a[name]["kernel"].astype(F32)  # noqa: E731
        angles = rotary_angles(sh.theta, sh.head_dim, positions)
        q = _rotate(_norm(jnp.einsum("sd,dhf->shf", u, w_of("q_proj")),
                          a["q_norm"]["scale"], sh.eps), angles)
        k = _rotate(_norm(jnp.einsum("sd,dhf->shf", u, w_of("k_proj")),
                          a["k_norm"]["scale"], sh.eps), angles)
        v = jnp.einsum("sd,dhf->shf", u, w_of("v_proj"))
        angles = rotary_angles(sh.theta, sh.index_dim, positions)
        qi = _rotate(jnp.einsum("sd,dhf->shf", u, w_of("qi_proj")), angles)
        ki = _rotate(jnp.einsum("sd,dhf->shf", u, w_of("ki_proj")),
                     angles)[:, 0]
        return q, k, v, qi, ki, u @ a["w_proj"].astype(F32)


def _index_scores(qi, w, ki, sh: Shape):
    """I [queries, s] of queries' qI [queries, heads, d] and w [queries,
    heads] against every token's kI [s, d]."""
    products = jax.nn.relu(jnp.einsum("qhd,sd->qhs", qi, ki))
    return jnp.einsum("qh,qhs->qs", w, products) \
        * (sh.index_heads ** -0.5 * sh.index_dim ** -0.5)


def _select(scores, allowed, sh: Shape):
    """The mask [queries, s] of each query's `topk` allowed tokens of
    largest score: exact, ties to the token that stands earlier (`top_k`
    puts the lower index first among equals); every allowed token of a
    query with no more than topk."""
    k = min(sh.topk, scores.shape[1])
    # (0.0 and -0.0 are equal, and `top_k` orders them)
    scores = jnp.where(scores == 0, 0.0, scores)
    _, at = jax.lax.top_k(jnp.where(allowed, scores, -jnp.inf), k)
    chosen = jnp.zeros(scores.shape, bool).at[
        jnp.arange(scores.shape[0])[:, None], at].set(True)
    return chosen & allowed


def _attend(q, k, v, mask, sh: Shape):
    """softmax over the masked tokens [queries, s] of q . k / head_dim^1/2,
    times v: [queries, heads, head_dim]. Head h reads kv head h // (H/G)."""
    group = sh.heads // sh.kv_heads
    queries = q.reshape(q.shape[0], sh.kv_heads, group, sh.head_dim)
    logits = jnp.einsum("qgjd,sgd->qgjs", queries, k) * sh.head_dim ** -0.5
    probs = jax.nn.softmax(
        jnp.where(mask[:, None, None, :], logits, -jnp.inf), axis=-1)
    return jnp.einsum("qgjs,sgd->qgjd", probs, v).reshape(q.shape)


@functools.partial(jax.jit, static_argnames=("sh",))
def _attention_block(q, qi, w, order, branch, k, v, ki, orders, branches,
                     fed, *, sh: Shape):
    """A block of queries against every token: their scores, the
    reference's own selection, and the attention over it, or, for a query
    that `fed` [queries, k] gives indices for (-1: none), over those."""
    with jax.default_matmul_precision("highest"):
        allowed = (orders[None, :] <= order[:, None]) & (
            (branches[None, :] == 0) | (branches[None, :] == branch[:, None]))
        scores = _index_scores(qi, w, ki, sh)
        own = _select(scores, allowed, sh)
        mask = own
        if fed is not None:
            told = jnp.zeros(own.shape, bool).at[
                jnp.arange(own.shape[0])[:, None],
                jnp.where(fed >= 0, fed, own.shape[1])].set(
                    True, mode="drop")
            mask = jnp.where((fed >= 0).any(-1, keepdims=True),
                             told & allowed, own)
        return _attend(q, k, v, mask, sh), scores, own


@jax.jit
def _add_projected(x, attended, w_o):
    with jax.default_matmul_precision("highest"):
        return x + jnp.einsum("shf,hfd->sd", attended, w_o.astype(F32))


def attention_layer(x, p, positions, branch, sh: Shape, rows=None,
                    fed=None, detailed: bool = False):
    """x + Wo . attention for one layer on x [s, hidden]. `fed` [s, k]
    int: for every token the indices (in the array) of the tokens it
    attends in place of the reference's own selection, -1 where a slot is
    empty (a token with none keeps its own). `detailed`: also {"cached":
    (k, v, kI) of every token, "attended", "index_scores", "selected": at
    `rows`}, as numpy arrays on the host (a block's rows leave the device
    as the block ends). Every token's k, v and kI stand whole; q, qI and w
    are a block's at a time."""
    def keys(x, at):
        _, k, v, _, ki, _ = _project(x, p, at, sh=sh)
        return k, v, ki

    k, v, ki = _row_blocks(keys, x, positions)
    s = x.shape[0]
    order = jnp.arange(s)
    wanted = rows.tolist() if detailed else []
    keep = set(wanted)
    out, kept_rows, gave_rows, scores, own = [], [], [], [], []
    for at in range(0, s, QUERY_BLOCK):
        upto = min(at + QUERY_BLOCK, s)
        q, _, _, qi, _, w = _project(x[at:upto], p, positions[at:upto],
                                     sh=sh)
        gave, sc, sel = _attention_block(
            q, qi, w, order[at:upto], branch[at:upto], k, v, ki, order,
            branch,
            None if fed is None else jnp.asarray(fed[at:upto], jnp.int32),
            sh=sh)
        out.append(_add_projected(x[at:upto], gave,
                                  p["attn"]["o_proj"]["kernel"]))
        mine = sorted(keep.intersection(range(at, upto)))
        if mine:
            local = jnp.asarray(mine) - at
            kept_rows += mine
            gave_rows.append(np.asarray(gave[local]))
            scores.append(np.asarray(sc[local]))
            own.append(np.asarray(sel[local]))
        jax.block_until_ready(out[-1])
    x = jnp.concatenate(out)
    if not detailed:
        return x
    flat = lambda a: np.asarray(a).reshape(s, -1)  # noqa: E731
    back = np.argsort(np.argsort(wanted))     # `kept_rows` is ascending
    return x, {"cached": (flat(k), flat(v), np.asarray(ki)),
               "attended": np.concatenate(gave_rows)[back],
               "index_scores": np.concatenate(scores)[back],
               "selected": np.concatenate(own)[back]}


@functools.partial(jax.jit, static_argnames=("sh",))
def _route(x, p, routes, *, sh: Shape):
    """The expert layer's input u, the router's probabilities [s, E] and
    each position's weight for each expert [s, E] (0 where not chosen):
    over the reference's own top-k, or over `routes` [s, k]."""
    with jax.default_matmul_precision("highest"):
        u = _norm(x, p["mlp_norm"]["scale"], sh.eps)
        probs = jax.nn.softmax(u @ p["moe"]["router"].astype(F32), axis=-1)
        if routes is None:
            _, routes = jax.lax.top_k(probs, sh.experts_per_token)
        chosen = jnp.zeros(probs.shape, bool).at[
            jnp.arange(probs.shape[0])[:, None], routes].set(True)
        picked = jnp.where(chosen, probs, 0.0)
        return u, probs, picked / picked.sum(-1, keepdims=True)


@functools.partial(jax.jit, donate_argnums=(0,))
def _expert_block(out, u, weights, w_gate, w_up, w_down):
    """out + the sum over a block of experts of weight[s, e] * E_e(u[s]),
    in out's buffer."""
    with jax.default_matmul_precision("highest"):
        hidden = jax.nn.silu(jnp.einsum("sd,edf->esf", u, w_gate.astype(F32))) \
            * jnp.einsum("sd,edf->esf", u, w_up.astype(F32))
        each = jnp.einsum("esf,efd->esd", hidden, w_down.astype(F32))
        return out + jnp.einsum("se,esd->sd", weights, each)


def expert_layer(x, p, sh: Shape, routes=None):
    """x + the held experts' part of the routed sum on x [s, hidden], and
    the router's probabilities [s, E]."""
    return _row_blocks(lambda x, routes: _expert_rows(x, p, sh, routes),
                       x, routes)


def _expert_rows(x, p, sh: Shape, routes):
    u, probs, weights = _route(x, p, routes, sh=sh)
    first, count = sh.held
    experts = p["moe"]
    routed = jnp.zeros_like(x)
    for at in range(0, count, EXPERT_BLOCK):
        upto = min(at + EXPERT_BLOCK, count)
        routed = _expert_block(
            routed, u, weights[:, first + at:first + upto],
            experts["w_gate"][at:upto], experts["w_in"][at:upto],
            experts["w_out"][at:upto])
    return x + routed, probs


@jax.jit
def _head_block(n, lm_head):
    with jax.default_matmul_precision("highest"):
        return n @ lm_head.astype(F32)


def logits(params: Dict[str, Any], tokens, config: Dict[str, Any], *,
           positions=None, branch=None, embed_scale=None,
           routes: Optional[list] = None, selection: Optional[dict] = None,
           rows=None, details=None):
    """tokens [s] -> logits [s, vocab], float32 (numpy); `config` holds the
    published keys (`num_hidden_layers`, `sa_config` among them) and
    optionally `held_experts`. `positions`, `branch` [s]: several sequences
    with a common beginning as one array (the module's docstring).
    `embed_scale` [s, hidden] multiplies the embedded tokens (the parity
    check's wobble). `routes`: per layer, [s, k] expert ids to follow.
    `selection`: {layer: int [s, k]} the indices (in the array) of the
    tokens each token attends in that layer, -1 for an empty slot, in place
    of the reference's own selection.
    `rows`: the indices whose logits are wanted (all). `details`: the
    layers of which to return more; then (logits, {"probs": per layer the
    router's [s, E], and per layer of `details` {"cached", "attended",
    "index_scores", "selected"} as `attention_layer` gives them})."""
    sh = shape_of(config)
    tokens = jnp.asarray(tokens, jnp.int32)
    everyone = jnp.arange(tokens.shape[0])
    positions = everyone if positions is None \
        else jnp.asarray(positions, jnp.int32)
    branch = jnp.zeros_like(everyone) if branch is None \
        else jnp.asarray(branch, jnp.int32)
    wanted = everyone if rows is None else jnp.asarray(rows, jnp.int32)
    x = jnp.take(params["embed"], tokens, axis=0).astype(F32)
    if embed_scale is not None:
        x = x * embed_scale
    probs, more = [], {}
    for i in range(config["num_hidden_layers"]):
        p = params[f"layer_{i}"]
        fed = None if selection is None else selection.get(i)
        if details and i in details:
            x, more[i] = attention_layer(x, p, positions, branch, sh,
                                         wanted, fed, detailed=True)
        else:
            x = attention_layer(x, p, positions, branch, sh, wanted, fed)
        x, own = expert_layer(
            x, p, sh, None if routes is None
            else jnp.asarray(routes[i], jnp.int32))
        probs.append(own)
        x.block_until_ready()       # a layer at a time
    n = _norm(x[wanted], params["final_norm"]["scale"], sh.eps)
    head = params["lm_head"]["kernel"]
    # (on the host: 1.7k rows of a 152k vocabulary are 1 GB)
    out = np.concatenate(
        [np.asarray(_head_block(n, head[:, at:at + VOCAB_BLOCK]))
         for at in range(0, head.shape[1], VOCAB_BLOCK)], -1)
    if details is not None:
        return out, dict(more, probs=probs)
    return out
