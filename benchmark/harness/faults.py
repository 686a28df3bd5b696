"""Faults planted under the timed path, to show that `correct` comes out
false: used by the tests (tests/bench_suite) and, on the chip, by
tools/limits.py to read what each fault does to each number compared. No
run of the benchmark itself plants one."""
import numpy as np


def state_unchanged(step_fn):
    """A step that returns its state as it got it."""
    def step(params, state, toks, labs, lr=None):
        import jax
        keep = jax.tree_util.tree_map(lambda x: x + 0, (params, state))
        loss, _, _ = step_fn(params, state, toks, labs, lr)
        return (loss,) + keep
    return step


def half_batch(step_fn):
    """Half of the batch left out, the mean taken over the rest: the first
    half of the rows stands in for the second, so shapes stay as compiled."""
    def step(params, state, toks, labs, lr=None):
        import jax.numpy as jnp
        half = toks.shape[0] // 2
        toks = jnp.concatenate([toks[:half], toks[:half]])
        labs = jnp.concatenate([labs[:half], labs[:half]])
        return step_fn(params, state, toks, labs, lr)
    return step


def alter_token(engine):
    """A token altered where it is produced: every decode step hands the
    scheduler each slot's token plus one (every request of two tokens or
    more is hit, so any sample of the finished requests shows it)."""
    decode = engine.decode
    vocab = engine._model.cfg.vocab_size

    def wrapped():
        return (np.array(decode()) + 1) % vocab
    engine.decode = wrapped
