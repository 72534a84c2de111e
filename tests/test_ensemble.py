"""Ensemble behaviour (paper claims C1 + C2): one forward call over N
models, shared memory accounting, paper-schema responses."""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import smoke_model
from repro.core import Ensemble, EnsembleMember


def _members(n=3, C=8):
    cfg, model, _ = smoke_model("yi-9b")
    members = []
    for i in range(n):
        params = model.init(jax.random.PRNGKey(100 + i))

        def apply(p, batch, _m=model, _c=C):
            return _m.forward(p, batch)[:, -1, :_c]

        members.append(EnsembleMember(f"member_{i}", apply, params, C))
    return members


@pytest.fixture(scope="module")
def ensemble():
    return Ensemble(_members(), max_batch=8)


def test_single_forward_matches_individual_calls(ensemble):
    """The fused ensemble forward must equal per-member evaluation."""
    batch = {"tokens": np.ones((2, 8), np.int32)}
    fused = ensemble.forward(batch)
    for m in ensemble.members:
        solo = m.apply(m.params, {"tokens": jnp.asarray(batch["tokens"])})
        np.testing.assert_allclose(np.asarray(fused[m.name]),
                                   np.asarray(solo), rtol=2e-5, atol=2e-5)


def test_paper_response_schema(ensemble):
    """{'model_i': ['class', ...]} exactly as in the paper (§2.3)."""
    batch = {"tokens": np.ones((3, 8), np.int32)}
    resp = ensemble.respond(batch)
    for i in range(len(ensemble.members)):
        key = f"model_{i}"
        assert key in resp
        assert len(resp[key]) == 3
        assert all(isinstance(c, str) for c in resp[key])
    assert "ensemble" in resp and len(resp["ensemble"]) == 3


def test_or_policy_more_sensitive_than_and(ensemble):
    batch = {"tokens": np.random.default_rng(0).integers(
        0, 500, (6, 8)).astype(np.int32)}
    for cls in range(4):
        d_or = np.asarray(ensemble.detect(batch, positive_class=cls,
                                          threshold=0.12,
                                          policy="or")["ensemble"])
        d_and = np.asarray(ensemble.detect(batch, positive_class=cls,
                                           threshold=0.12,
                                           policy="and")["ensemble"])
        assert (d_and <= d_or).all()


def test_variable_batch_sizes_one_compile_per_bucket(ensemble):
    before = ensemble.num_compilations
    for n in (1, 2, 3, 5, 8):
        batch = {"tokens": np.ones((n, 8), np.int32)}
        out = ensemble.forward(batch)
        assert next(iter(out.values())).shape[0] == n
    assert ensemble.num_compilations <= len(
        ensemble.batch_buckets.sizes)


def test_memory_ledger_counts_all_members(ensemble):
    ledger = ensemble.memory_ledger(n_chips=2)
    assert len(ledger.entries) == len(ensemble.members)
    assert ledger.bytes_per_chip > 0
    assert ledger.fits()
    rep = ledger.report()
    assert "FITS" in rep


def test_forward_takes_weights_as_arguments():
    """Member params reach the batched jit as arguments.  Captured as
    constants they are copied into every compiled bucket program, which
    at published widths exhausts the host while lowering."""
    ens = Ensemble(_members(n=2), max_batch=4)
    was = jax.config.jax_captured_constants_warn_bytes
    jax.config.update("jax_captured_constants_warn_bytes", 1)
    try:
        with warnings.catch_warnings():
            warnings.filterwarnings("error", message=".*constants were "
                                    "captured")
            out = ens.forward({"tokens": np.ones((3, 8), np.int32)})
    finally:
        jax.config.update("jax_captured_constants_warn_bytes", was)
    assert next(iter(out.values())).shape[0] == 3
