"""The tests' greedy oracle: the model's plain forward over the whole
sequence, nothing cached, a call a generated token. What an engine's
greedy tokens are held against, whatever it keeps between steps; a plain
module, not a fixture, and nothing under `ray_tpu/` imports it."""

import numpy as np

import jax


def model_forward(module, params):
    """`forward` for a flax module whose no-cache call is `apply(vars,
    tokens) -> [batch, length, vocab]`: jitted, so one length compiles
    once."""
    return jax.jit(lambda tokens: module.apply({"params": params}, tokens))


def rowwise(logits_of):
    """`forward` from a reference that takes ONE sequence `[length]` and
    returns `[length, vocab]` (benchmarks/reference/*_ref.py)."""
    return lambda tokens: np.stack(
        [np.asarray(logits_of(row), np.float32) for row in tokens])


def plain_greedy(forward, prompts, max_new, length=None):
    """Greedy continuations of `prompts`, `max_new` tokens each.

    `forward(tokens [batch, length] int32) -> logits [batch, length,
    vocab]` is a CAUSAL model's full-sequence forward. The prompts are
    batched and right-padded with zeros to one `length` (the longest
    prompt + `max_new` unless given: pass one to share a compile between
    calls), so `forward` sees one shape: row `n - 1` of a sequence of `n`
    does not see what is padded behind it. A token is the argmax of that
    row in float32, the LOWEST id on a tie (numpy's rule, and the
    sampler's greedy branch's)."""
    lengths = np.asarray([len(p) for p in prompts])
    if length is None:
        length = int(lengths.max()) + max_new
    assert lengths.min() > 0 and lengths.max() + max_new <= length
    tokens = np.zeros((len(prompts), length), np.int32)
    for row, prompt in enumerate(prompts):
        tokens[row, :len(prompt)] = prompt
    rows = np.arange(len(prompts))
    for _ in range(max_new):
        logits = np.asarray(forward(tokens), np.float32)
        tokens[rows, lengths] = logits[rows, lengths - 1].argmax(-1)
        lengths = lengths + 1
    return [tokens[row, n - max_new:n].tolist()
            for row, n in zip(rows, lengths)]
