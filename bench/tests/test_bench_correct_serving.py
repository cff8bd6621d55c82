"""``correct`` on a serving cell: true for the program as configured,
false for its control and for each fault a one-chip serving cell can have
(there is no exchange between chips on one chip)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.query import scheduler as sched_mod

from bench import tracing
from bench.tests import faults, tiny
from bench.tests.test_bench_arithmetic import STAGES


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make(tmp_path_factory.mktemp("tiny"))


def test_the_program_as_configured_is_correct(root):
    r = faults.run(root, "lj-ppr-refresh")
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0


def test_the_control_a_quarter_of_the_index_is_not_correct(root):
    r = faults.run(root, "lj-ppr-refresh", control="index")
    assert not r["correct"]
    assert (r["checks"]["ppr_mass_deficit"]["value"]
            > r["checks"]["ppr_mass_deficit"]["limit"])


def _shifted_topk(orig):
    def topk(counts, k):
        top = orig(counts, k)
        return (top + 1) % counts.shape[0]
    return topk


def _half_the_walks(orig):
    def run_wave(self, start, uniform, qid, t_cap, k_wave, W_b, Q_b):
        qid = np.array(qid)
        qid[1::2] = Q_b                    # every other walk is not tallied
        return orig(self, start, uniform, qid, t_cap, k_wave, W_b, Q_b)
    return run_wave


def _state_unchanged(orig):
    def run_wave(self, *args):
        counts, clean, dt = orig(self, *args)
        return jnp.zeros_like(counts), clean, dt
    return run_wave


@pytest.mark.parametrize("fault, target, name, wrap", [
    ("an answer altered where it is produced", sched_mod, "_topk_stable",
     _shifted_topk),
    ("half of the walks left out, the mean over the rest",
     sched_mod.QueryScheduler, "_run_wave", _half_the_walks),
    ("a wave that returns its state unchanged", sched_mod.QueryScheduler,
     "_run_wave", _state_unchanged),
])
def test_a_broken_timed_path_is_not_correct(root, monkeypatch, fault, target,
                                            name, wrap):
    monkeypatch.setattr(target, name, wrap(getattr(target, name)))
    r = faults.run(root, "lj-ppr-refresh")
    assert not r["correct"], fault


def test_open_loop_cell_is_correct_and_reports_its_latency(root):
    r = faults.run(root, "lj-ppr-interactive")
    assert r["correct"], r["checks"]
    assert r["metrics"]["answer_p90_s"]["value"] > 0


def test_a_traced_run_reports_every_stage_of_the_wave(root, monkeypatch,
                                                      capsys):
    """A traced run turns the program's stage spans on and reads each
    stage metric; the trace holds one ``repro.scheduler.step_wave`` span
    per wave the counters saw. A CPU trace has no device plane, so one
    device op per wave, over its ``repro.wave.device_wait`` span, stands
    in for the chip's."""
    load = tracing.load_events

    def with_a_device(xplane):
        events = load(xplane)
        return events + [
            ("/device:TPU:0", line, name, t, d)
            for _, _, span, t, d in events if span == "repro.wave.device_wait"
            for line, name in (("XLA Modules", "jit_wave(0)"),
                               ("XLA Ops", "fusion.0"))]

    monkeypatch.setattr(tracing, "load_events", with_a_device)
    r = faults.run(root, "lj-ppr-refresh", trace=True)
    out = capsys.readouterr().out
    assert r["correct"], r["checks"]
    for name in STAGES:
        assert r["metrics"][name]["value"] > 0, name
    assert r["metrics"]["wave.tally_mb"]["value"] == 4 * 3000 * 4 / 1e6
    line = next(x for x in out.splitlines()
                if "repro.scheduler.step_wave spans in the trace" in x)
    assert line.endswith("(ok)"), line
    assert "of the idle time) under repro." in out
