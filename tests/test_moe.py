"""The routed experts' two schedules (`models.moe.held_expert_sum`): every
held expert on every token, and the pairs sorted by expert through grouped
products (`ops.grouped_matmul`). One sum: the sorted form against the dense
one at every way the pairs can fall, the kernel under the TPU interpreter
against the plain XLA grouped form, and the rule that chooses between them
as a function of static shapes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import moe
from ray_tpu.ops import grouped_matmul as gm

WIDTH, MLP = 128, 256      # whole lane tiles: what the kernel takes


def layer(seed, tokens, k, experts, first, held, gated, dtype=jnp.float32):
    """(x, chosen, weights, mask, w_in, w_out, first, w_gate) of a seeded
    layer: `k` distinct experts of `experts` a token."""
    rng = np.random.default_rng(seed)
    chosen = np.stack([rng.permutation(experts)[:k] for _ in range(tokens)])
    matrix = lambda *shape: jnp.asarray(
        rng.standard_normal(shape) / np.sqrt(shape[-2]), dtype)
    return (jnp.asarray(rng.standard_normal((tokens, WIDTH)), dtype),
            jnp.asarray(chosen, jnp.int32),
            jnp.asarray(rng.random((tokens, k)), jnp.float32),
            jnp.ones((tokens,), bool),
            matrix(held, WIDTH, MLP), matrix(held, MLP, WIDTH), first,
            matrix(held, WIDTH, MLP) if gated else None)


def every_token_on_one_expert(args):
    """Total imbalance: every token's k choices are the same k experts, the
    first of them held."""
    x, chosen, *rest = args
    first, k = rest[4], chosen.shape[1]
    return (x, jnp.broadcast_to(first + jnp.arange(k, dtype=jnp.int32),
                                chosen.shape), *rest)


def masked_tokens(args):
    x, chosen, weights, mask, *rest = args
    return (x, chosen, weights, mask.at[::3].set(False), *rest)


def a_token_held_elsewhere(args):
    """Token 1's k choices are all held on another chip."""
    x, chosen, *rest = args
    first, held = rest[4], rest[2].shape[0]
    elsewhere = (first + held + jnp.arange(chosen.shape[1])) % 16
    return (x, chosen.at[1].set(elsewhere.astype(jnp.int32)), *rest)


FALLS = {"seeded": lambda args: args,
         "one_expert": every_token_on_one_expert,
         "masked": masked_tokens,
         "elsewhere": a_token_held_elsewhere}


def agree(got, want):
    """`pairs` equal exactly, `out` equal to float32 rounding."""
    (out, pairs), (want_out, want_pairs) = got, want
    assert pairs.dtype == jnp.int32 and out.dtype == jnp.float32
    assert pairs.tolist() == want_pairs.tolist()
    scale = float(jnp.abs(want_out).max())
    assert float(jnp.abs(out - want_out).max()) <= 2e-6 * scale


@pytest.mark.parametrize("fall", sorted(FALLS))
@pytest.mark.parametrize("gated", [False, True])
@pytest.mark.parametrize("first,held", [(0, 16), (4, 8)])
def test_the_sorted_form_is_the_dense_sum(first, held, gated, fall):
    """All held and a share held (4 .. 11 of 16), three choices a token,
    50 tokens: 150 pairs are not whole row tiles."""
    args = FALLS[fall](layer(7, 50, 3, 16, first, held, gated))
    want = moe._dense_expert_sum(*args)
    if fall == "elsewhere" and held < 16:
        assert not bool(((args[1][1] >= first)
                         & (args[1][1] < first + held)).any())
    agree(jax.jit(moe._sorted_expert_sum, static_argnums=(6,))(*args), want)


@pytest.mark.parametrize("fall", sorted(FALLS))
@pytest.mark.parametrize("gated", [False, True])
def test_the_kernel_under_the_interpreter_is_the_dense_sum(monkeypatch,
                                                           gated, fall):
    """The same sum with the grouped products through the Pallas kernels
    (the path a TPU takes), row tiles of 128 against groups of ~19 and of
    50 rows: tiles shared by several groups, a last tile past every group."""
    monkeypatch.setattr(gm, "grouped_kernel", lambda *_: "pallas")
    args = FALLS[fall](layer(11, 50, 3, 16, 4, 8, gated))
    agree(moe._sorted_expert_sum(*args), moe._dense_expert_sum(*args))


def test_no_pair_is_dropped_at_any_imbalance():
    """Every token on expert 5, all of them: a capacity of 1.25 x the mean
    would keep a twelfth."""
    args = every_token_on_one_expert(layer(3, 300, 2, 16, 5, 4, True))
    out, pairs = moe._sorted_expert_sum(*args)
    assert pairs.tolist() == [300, 300, 0, 0]
    agree((out, pairs), moe._dense_expert_sum(*args))


def test_bfloat16_matrices_take_float32_sums():
    """The serve path's types: bf16 operands, float32 sums, the hidden rows
    rounded to bf16 before `w_out` in both forms."""
    args = layer(5, 64, 4, 8, 0, 8, True, jnp.bfloat16)
    out, pairs = moe._sorted_expert_sum(*args)
    want, want_pairs = moe._dense_expert_sum(*args)
    assert pairs.tolist() == want_pairs.tolist()
    assert float(jnp.abs(out - want).max()) \
        <= 2e-2 * float(jnp.abs(want).max())


@pytest.mark.parametrize("sizes,rows", [
    ([0, 5, 0, 130, 1, 0], 256),       # empty groups, one over a tile's edge
    ([128, 128], 256),                 # whole tiles
    ([0, 0, 0], 128),                  # nothing held here was chosen
    ([300], 384),                      # one group, rows behind it
])
def test_the_plan_visits_every_tile_a_group_touches_once(sizes, rows):
    plan = gm.plan_visits(jnp.asarray(sizes, jnp.int32), rows)
    count = int(plan.count[0])
    assert plan.group.shape == (rows // gm.ROW_TILE + len(sizes) - 1,)
    assert plan.offsets.tolist() == [0] + np.cumsum(sizes).tolist()
    want = [(g, t) for g, (start, size) in enumerate(
        zip(plan.offsets.tolist(), sizes)) if size
        for t in range(start // gm.ROW_TILE,
                       (start + size - 1) // gm.ROW_TILE + 1)]
    assert list(zip(plan.group.tolist(), plan.tile.tolist()))[:count] == want
    # what lies past the count repeats the last visit: no block moves
    assert set(zip(plan.group.tolist()[count:], plan.tile.tolist()[count:])) \
        <= {want[-1] if want else (len(sizes) - 1, 0)}


# The four expert configurations' programs: a decode step's rows and each
# prefill bucket, with the experts' [l, f] (benchmarks/configs/*.json).
SHAPES = {"nemotron": ((96, 32, 64, 128, 256), 1024, 2688),
          "sarvam": ((48, 32, 64, 128, 256), 4096, 2048),
          "keye": ((48, 64, 128, 256, 512), 2048, 768),
          "xing": ((48, 32, 64, 128, 256, 512), 3584, 1024)}


@pytest.mark.parametrize("name,tokens", [
    (name, tokens) for name, (counts, _, _) in sorted(SHAPES.items())
    for tokens in counts])
def test_the_rule_reads_static_shapes_alone(name, tokens):
    """Sorted from 512 tokens a call, dense under it: Xing's and Keye's
    largest bucket and nothing else the repo runs."""
    _, width, mlp = SHAPES[name]
    assert moe.sorted_form(tokens, width, mlp) == (tokens >= 512)


def test_toy_widths_stay_dense_at_any_length():
    """Matrices that are not whole lane tiles are none the kernel takes."""
    assert not moe.sorted_form(4096, 32, 48)
    assert not moe.sorted_form(512, 128, 48)


def test_the_engine_counts_its_buckets_by_the_same_rule():
    held = jax.ShapeDtypeStruct((4, 128, 256), jnp.float32)
    tree = {"layers_1": {"moe": {"routed": {"w_in": held, "w_gate": held,
                                            "router": jax.ShapeDtypeStruct(
                                                (128, 16), jnp.float32)}}}}
    assert moe.sorted_buckets(tree, (64, 256, 512, 1024)) == {512, 1024}
    assert moe.sorted_buckets(tree, (64, 256)) == frozenset()
    assert moe.sorted_buckets({"mlp": {"kernel": held}}, (512,)) is None


def _program(fn, args):
    def expert_layer(*arrays):
        return fn(*arrays[:6], args[6], *arrays[6:])
    arrays = [a for a in args[:6] + args[7:] if a is not None]
    return jax.jit(expert_layer).lower(*arrays).as_text()


@pytest.mark.parametrize("gated", [False, True])
@pytest.mark.parametrize("tokens", [48, 256])
def test_under_the_rule_the_program_is_the_dense_form_alone(tokens, gated):
    """At 256 tokens and fewer `held_expert_sum` lowers to the StableHLO
    text of the dense function: every program that stays on it is the
    parent's."""
    args = layer(1, tokens, 3, 16, 4, 8, gated)
    assert _program(moe.held_expert_sum, args) \
        == _program(moe._dense_expert_sum, args)


def test_from_the_rule_up_the_program_is_the_sorted_form():
    args = layer(1, 512, 3, 16, 4, 8, True)
    text = _program(moe.held_expert_sum, args)
    assert text == _program(moe._sorted_expert_sum, args)
    assert text != _program(moe._dense_expert_sum, args)
    assert "stablehlo.sort" in text
