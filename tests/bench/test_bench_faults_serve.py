"""A serving cell's run with its timed path broken underneath (CPU
rehearsal sizes, interpret kernels): the sound run is correct under the
rehearsal limit, and a token altered where it is produced makes
``correct`` come out false."""
import dataclasses
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import run  # noqa: E402
from bench.cells import serve  # noqa: E402

# smoke-size readings (CPU, seeds 1-4): logit_gap <= 0.18; the control
# reads >= 0.78
LIMITS = {"logit_gap": {"limit": 0.4}}
SEED = 3


def _run():
    r = run.run_cell("dsmoe-decode", SEED, 0.3, False, True, limits=LIMITS)
    assert r["attempted"] > 0
    return r


def test_sound_run_is_correct():
    r = _run()
    assert r["_verdict"], r["checks"]


def test_token_altered(monkeypatch):
    real = serve.build

    def build(*a, **k):
        prog = real(*a, **k)
        generate, vocab = prog.engine.generate, prog.cfg.vocab_size

        def altered(batch, **kw):
            res = generate(batch, **kw)
            toks = res.tokens.at[:, 2].set((res.tokens[:, 2] + 1) % vocab)
            return dataclasses.replace(res, tokens=toks)
        prog.engine.generate = altered
        return prog
    monkeypatch.setattr(serve, "build", build)
    r = _run()
    assert not r["_verdict"]
    assert r["checks"]["logit_gap"]["value"] > LIMITS["logit_gap"]["limit"]
