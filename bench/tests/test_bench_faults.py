"""The comparison that decides ``correct`` fails what it must, at a size
the CPU holds: the float8 control put in the program's place in the
check, and the timed path broken underneath a whole run (the device
check skipped).

Tiny readings (CPU, seeds 1-12, ``tiny.tiny_cell``): sound runs read a
mean logit gap of 0 to 9.7e-7, the float8 control 3.8e-5 to 1.8e-4; the
tiny limit sits between them."""
import time

import jax
import jax.numpy as jnp
import pytest

import tiny
from control import CONTROL
from harness import run_cell


def _run(seed, hook=None, control=None):
    return run_cell(tiny.tiny_cell(), seed, 2.0, False,
                    time.perf_counter(), require_tpu=False,
                    engine_hook=hook, control=control, cache=False)


@pytest.mark.parametrize("seed", [1, 3, 5])
def test_control_fails_and_program_passes(seed):
    out = _run(seed, control=CONTROL)
    limit = out["checks"]["logit_gap_mean"]["limit"]
    assert not out["correct"], out["checks"]
    assert out["checks"]["logit_gap_mean"]["value"] == \
        out["readings"]["control"]["mean"] > limit
    assert out["readings"]["program"]["mean"] <= limit


def _alter_token(eng):
    """A served token altered where the step produces it."""
    orig, v = eng._chunk, eng.cfg.vocab

    def bad(*a):
        emitted, live, caches = orig(*a)
        return emitted.at[0].set((emitted[0] + 1) % v), live, caches
    eng._chunk = bad


def _state_unchanged(eng):
    """A decode step that hands back the cache it was given."""
    orig = eng._chunk

    def bad(p, toks, caches, *rest):
        before = jax.tree.map(jnp.copy, caches)
        emitted, live, _ = orig(p, toks, caches, *rest)
        return emitted, live, before
    eng._chunk = bad


def _admission_unwritten(eng):
    """An admission whose prefill cache never reaches its slot."""
    eng._write = lambda full, pre, row, slot: full


@pytest.mark.parametrize("fault", [_alter_token, _state_unchanged,
                                   _admission_unwritten])
@pytest.mark.parametrize("seed", [2, 7])
def test_broken_timed_path_is_not_correct(fault, seed):
    out = _run(seed, hook=fault)
    assert not out["correct"], out["checks"]
