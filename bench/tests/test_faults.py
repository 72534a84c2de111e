"""The comparison fails what it must: a whole run on the CPU, the chip
check skipped, with the timed path broken underneath, reads
``correct: false``; and the control (the reference with every linear
layer in float8, in the program's place) fails the limit that bf16 runs
pass."""

import pytest

import checkout

STATE_UNCHANGED = """
from repro.core.engine import InferenceEngine
import jax, jax.numpy as jnp
orig = InferenceEngine.decode_sample
def broken(self, token, state, samp, ctr):
    keep = jax.tree_util.tree_map(jnp.copy, state)
    toks, _, ctr = orig(self, token, state, samp, ctr)
    return toks, keep, ctr
InferenceEngine.decode_sample = broken
"""

TOKEN_ALTERED = """
from repro.core.engine import InferenceEngine
orig = InferenceEngine.decode_sample
def broken(self, token, state, samp, ctr):
    toks, state, ctr = orig(self, token, state, samp, ctr)
    return (toks + 1) % self.model.config.vocab_size, state, ctr
InferenceEngine.decode_sample = broken
"""

TOP_P_IGNORED = """
from repro.core.engine import InferenceEngine
import jax.numpy as jnp
orig = InferenceEngine.decode_sample
def broken(self, token, state, samp, ctr):
    samp = dict(samp, top_p=jnp.ones_like(samp["top_p"]))
    return orig(self, token, state, samp, ctr)
InferenceEngine.decode_sample = broken
"""

TEMPERATURE_IGNORED = """
from repro.core.engine import InferenceEngine
import jax.numpy as jnp
orig = InferenceEngine.decode_sample
def broken(self, token, state, samp, ctr):
    t = samp["temperature"]
    samp = dict(samp, temperature=jnp.where(t > 0, 1.0, t))
    return orig(self, token, state, samp, ctr)
InferenceEngine.decode_sample = broken
"""

ANSWER_ALTERED = """
from repro.core.ensemble import Ensemble
import jax.numpy as jnp
orig = Ensemble.forward
def broken(self, batch):
    return {k: jnp.roll(v, 1, axis=-1) for k, v in orig(self, batch).items()}
Ensemble.forward = broken
"""


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return checkout.make(tmp_path_factory.mktemp("checkout"))


@pytest.mark.parametrize("cell,fault", [
    ("tiny.chat", STATE_UNCHANGED),
    ("tiny.chat", TOKEN_ALTERED),
    ("tiny.chat", TOP_P_IGNORED),
    ("tiny.chat", TEMPERATURE_IGNORED),
    ("tiny.batch", TOKEN_ALTERED),
    ("tiny.infer", ANSWER_ALTERED),
])
def test_broken_path_is_not_correct(tiny, cell, fault):
    out = checkout.drive(tiny, fault + (
        f"out = run.run({cell!r}, 424242, 4.0, False, "
        "require_tpu=False, bench_dir=run.Path('bench'))\n"
        "print(json.dumps(out))\n"))
    assert out["correct"] is False
    c = out["checks"]["max_gap_sd"]
    assert c["value"] > c["limit"]


@pytest.mark.parametrize("cell", ["tiny.chat", "tiny.infer"])
def test_control_fails_the_limit(tiny, cell):
    readings = checkout.drive(tiny, (
        "spec = run.Spec(run.Path('bench'))\n"
        f"sess = run.Session(spec, {cell!r}, 77, require_tpu=False)\n"
        "wins = [(s, sess.window(sess.mix, s, 4.0, False)['records'])\n"
        "        for s in (77, 78, 79)]\n"
        "sess.close()\n"
        "got = [sess.compare(s, 4.0, r, control=True) for s, r in wins]\n"
        "print(json.dumps(got))\n"))
    limit = checkout.TINY_CONFIG["limits"][
        "infer" if cell.endswith("infer") else "generate"]["max_gap_sd"]
    print(readings)
    for r in readings:
        assert r["max_gap_sd"] <= limit < r["control_max_gap_sd"]


@pytest.mark.parametrize("cell", ["tiny.chat", "tiny.infer"])
def test_control_in_the_programs_place_is_not_correct(tiny, cell):
    out = checkout.drive(tiny, (
        f"out = run.run({cell!r}, 4343, 4.0, False, require_tpu=False, "
        "bench_dir=run.Path('bench'), control=True)\n"
        "print(json.dumps(out))\n"))
    assert out["correct"] is False
    c = out["checks"]["max_gap_sd"]
    assert c["value"] > c["limit"]
