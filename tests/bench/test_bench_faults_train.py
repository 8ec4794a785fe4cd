"""A training cell's run with its timed path broken underneath: the
harness's look for a chip is skipped (CPU rehearsal sizes, interpret
kernels) and the rest of the run is driven as on the chip.  The sound
run is correct under the rehearsal limits; each fault makes ``correct``
come out false by a compared number."""
import os
import sys

import jax

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import run  # noqa: E402
from bench.cells import train  # noqa: E402

# smoke-size readings (CPU, seeds 1-3): grad_gap <= 1.1e-2, change_gap
# <= 3.2e-3; the limits sit about twice above.  loss_gap is read but not
# compared, as in the chip cell's limits (its control reads no higher)
LIMITS = {"loss_gap": {"compared": False, "limit": None},
          "grad_gap": {"limit": 0.02}, "change_gap": {"limit": 0.006}}
SEED = 3


def _run():
    r = run.run_cell("dsmoe-train", SEED, 0.3, False, True, limits=LIMITS)
    assert r["attempted"] > 0
    return r


def _over(r):
    return [n for n, c in r["checks"].items() if c["value"] > c["limit"]]


def _broken(monkeypatch, wrap):
    real = train.compile_program

    def compile_program(*a, **k):
        prog = real(*a, **k)
        prog.compiled = wrap(prog)
        return prog
    monkeypatch.setattr(train, "compile_program", compile_program)


def test_sound_run_is_correct():
    r = _run()
    assert r["_verdict"], r["checks"]
    assert r["correct"] is False        # a rehearsal never reports true


def test_state_unchanged(monkeypatch):
    from repro.models.model_zoo import make_model

    def wrap(prog):
        loss = jax.jit(make_model(prog.cfg).loss)
        return lambda p, o, b: (p, o, {"loss": loss(p, b)[0]})
    _broken(monkeypatch, wrap)
    r = _run()
    assert not r["_verdict"]
    assert {"grad_gap", "change_gap"} <= set(_over(r))


def test_half_batch_left_out(monkeypatch):
    def wrap(prog):
        step = prog.compiled

        def half(p, o, b):
            rows = b["labels"].shape[0]
            labels = b["labels"].at[rows // 2:].set(-1)
            return step(p, o, {"tokens": b["tokens"], "labels": labels})
        return half
    _broken(monkeypatch, wrap)
    r = _run()
    assert not r["_verdict"]
    assert _over(r)
