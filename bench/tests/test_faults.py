"""A run with the timed path broken underneath must come out not correct.
The one fault these cells can have (no training step, no exchange between
chips) is an answer altered where it is produced: a fit's label or
density, a served label, or the serving kernel's acceptance bar ignored
or read from densities twice too large."""

import json
import os
import time

import jax
import numpy as np
import pytest

import harness
from test_rehearsal import CELLS, tiny


def run(name):
    cell = tiny(harness.load_cell(name))
    return harness.run_cell(cell, seed=11, seconds=1.0, trace=False,
                            t_start=time.perf_counter(),
                            devices=jax.devices()[:1], log=lambda m: None)


def moved_label(res):
    labels = np.array(res.labels)
    i = int(np.flatnonzero(labels >= 0)[0])
    labels[i] = (labels[i] + 1) % res.n_clusters
    return res._replace(labels=labels)


def scaled_density(res):
    dens = np.array(res.densities)
    dens[0] *= 1.001
    return res._replace(densities=dens)


@pytest.mark.parametrize("alter", [moved_label, scaled_density])
def test_fit_answer_altered(monkeypatch, alter):
    from repro.core import engine
    loop = engine._fit_loop
    monkeypatch.setattr(engine, "_fit_loop",
                        lambda *a, **k: alter(loop(*a, **k)))
    out = run("fit.blobs100k")
    assert out["correct"] is False, harness.check_lines(out)


@pytest.mark.parametrize("name", [c for c in CELLS if c.startswith("serve")])
def test_served_answer_altered(monkeypatch, name):
    from repro.serve.batching import Tenant
    assign = Tenant.assign_np

    def altered(self, q, valid):
        labels = np.array(assign(self, q, valid))
        labels[0] = (labels[0] + 1) % self.n_clusters
        return labels

    monkeypatch.setattr(Tenant, "assign_np", altered)
    out = run(name)
    assert out["correct"] is False, harness.check_lines(out)
    assert json.loads(json.dumps(out))["checks"]["answer_gap"]["ok"] is False


def bar_ignored(tenant):
    tenant._thr = jax.numpy.float32(0.0)


def densities_doubled(tenant):
    tenant._dens = tenant._dens * 2.0


@pytest.mark.parametrize("name", [c for c in CELLS if c.startswith("serve")])
@pytest.mark.parametrize("fault", [bar_ignored, densities_doubled])
def test_served_bar_broken(monkeypatch, name, fault):
    from repro.serve.batching import Tenant
    init = Tenant.__init__

    def broken(self, *a, **k):
        init(self, *a, **k)
        fault(self)

    monkeypatch.setattr(Tenant, "__init__", broken)
    out = run(name)
    assert out["correct"] is False, harness.check_lines(out)
    assert out["checks"]["labelled_share"]["ok"] is False
    assert out["checks"]["answer_gap"]["ok"] is False
