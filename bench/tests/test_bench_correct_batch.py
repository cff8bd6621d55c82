"""``correct`` on the batch cell: true for the program as configured,
false for its control and for each fault a one-chip batch cell can have."""
import dataclasses

import jax.numpy as jnp
import pytest

from repro import service
from repro.core.frogwild import FrogWildResult

from bench.tests import faults, tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make(tmp_path_factory.mktemp("tiny"))


def test_the_program_as_configured_is_correct(root):
    r = faults.run(root, "lj-batch")
    assert r["correct"], r["checks"]
    assert r["metrics"]["batch_s"]["value"] > 0


def test_the_control_a_quarter_of_the_frogs_is_not_correct(root):
    r = faults.run(root, "lj-batch", control="frogs")
    assert not r["correct"]


def _altered(orig):
    def walks(g, cfg, key):
        r = orig(g, cfg, key)
        return FrogWildResult(counts=jnp.roll(r.counts, 1),
                              pi_hat=jnp.roll(r.pi_hat, 1),
                              num_frogs=r.num_frogs)
    return walks


def _half_the_frogs(orig):
    def walks(g, cfg, key):
        return orig(g, dataclasses.replace(cfg, num_frogs=cfg.num_frogs // 2),
                    key)
    return walks


def _state_unchanged(orig):
    def walks(g, cfg, key):
        r = orig(g, cfg, key)
        return FrogWildResult(counts=jnp.zeros_like(r.counts),
                              pi_hat=jnp.zeros_like(r.pi_hat),
                              num_frogs=r.num_frogs)
    return walks


@pytest.mark.parametrize("fault, wrap", [
    ("an estimate altered where it is produced", _altered),
    ("half of the frogs left out, the mean over the rest", _half_the_frogs),
    ("an estimate that returns its state unchanged", _state_unchanged),
])
def test_a_broken_timed_path_is_not_correct(root, monkeypatch, fault, wrap):
    monkeypatch.setattr(service, "frogwild_walks",
                        wrap(service.frogwild_walks))
    r = faults.run(root, "lj-batch")
    assert not r["correct"], fault
