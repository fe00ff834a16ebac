"""A run with the timed path broken underneath comes out not correct.

Each case drives a whole tiny run on the CPU (the harness's look for a chip
skipped) with one fault planted in the program's engine: a served token
altered where it is produced, a stage run on another expert's weights (a
stale expert after a switch), and a verifier stage skipped.
"""
from __future__ import annotations

import pytest

from repro.core import CoServeSystem, RealEngine
from bench.lib.serve import VERIFIER
from bench.tests.tiny import TINY, run_tiny, tiny_root


def altered_token(monkeypatch):
    execute = RealEngine.execute

    def broken(self, ex, expert_id, batch):
        out, lat = execute(self, ex, expert_id, batch)
        return [(t + 1) % TINY["vocab_size"] for t in out], lat
    monkeypatch.setattr(RealEngine, "execute", broken)


def stale_expert(monkeypatch):
    execute = RealEngine.execute

    def broken(self, ex, expert_id, batch):
        other = [e for e in self.device_params if e != expert_id]
        return execute(self, ex, other[0] if other else expert_id, batch)
    monkeypatch.setattr(RealEngine, "execute", broken)


def skipped_stage(monkeypatch):
    route = CoServeSystem.route_followup

    def broken(self, req, expert_id, output):
        nxt = route(self, req, expert_id, output)
        return None if nxt is not None and nxt.expert_id == VERIFIER else nxt
    monkeypatch.setattr(CoServeSystem, "route_followup", broken)


@pytest.mark.parametrize("fault", [altered_token, stale_expert,
                                   skipped_stage])
def test_fault_makes_run_incorrect(tmp_path, monkeypatch, fault):
    fault(monkeypatch)
    r = run_tiny(tiny_root(tmp_path), "sc2-swap-short")
    assert r["correct"] is False
    assert r["failed"] > 0
