"""Each fault the cells can have, planted under the timed path, turns
``correct`` false while the rest of the run goes on as usual.  (One
chip: no cell has an exchange between chips to leave out.)"""
from __future__ import annotations

import dataclasses
import json

import numpy as np
import pytest

from bench import run


def _correct(root, capsys, workload):
    rc = run.main(["--workload", workload, "--seed", "2147483653",
                   "--seconds", "2", "--trace", "0"], root=root)
    assert rc == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    return res["correct"], res["checks"]


def test_retrieval_answer_altered_where_produced(tiny_root, capsys,
                                                 monkeypatch):
    from repro.serving.retrieval import RetrievalProgram
    emit = RetrievalProgram.emit

    def altered(self, state, req, slot, out, stats):
        done = emit(self, state, req, slot, out, stats)
        req.topk_ids[0] = (req.topk_ids[0] + 1) % self.rcfg.d
        return done

    monkeypatch.setattr(RetrievalProgram, "emit", altered)
    ok, checks = _correct(tiny_root, capsys, "tiny.zipf")
    assert not ok, checks


def test_retrieval_control_bfloat16_pool(tiny_root, capsys, monkeypatch):
    """The control: the program's own path with its (rows, m) pool
    stored in bfloat16, one step below the float32 the configuration
    states."""
    from repro.serving.retrieval import RetrievalProgram
    init = RetrievalProgram.__init__

    def narrow(self, rcfg, *args, **kw):
        init(self, dataclasses.replace(rcfg, table_dtype="bfloat16"),
             *args, **kw)

    monkeypatch.setattr(RetrievalProgram, "__init__", narrow)
    ok, checks = _correct(tiny_root, capsys, "tiny.zipf")
    assert not ok, checks
    assert checks["score_err"]["value"] > checks["score_err"]["limit"]


def test_lm_token_altered_where_produced(tiny_root, capsys, monkeypatch):
    from repro.serving.engine import LMSlotProgram
    step = LMSlotProgram.step

    def altered(self, params, state):
        out = np.array(step(self, params, state))
        out[0] = (out[0] + 1) % self.cfg.vocab
        return out

    monkeypatch.setattr(LMSlotProgram, "step", altered)
    ok, checks = _correct(tiny_root, capsys, "tiny.chat")
    assert not ok, checks


def test_lm_step_that_leaves_its_state_unchanged(tiny_root, capsys,
                                                 monkeypatch):
    from repro.serving.engine import LMSlotProgram

    def stale(self, params, state):
        # no decode: every slot gets back the token it was fed
        return np.asarray(state.tokens[:, 0])

    monkeypatch.setattr(LMSlotProgram, "step", stale)
    ok, checks = _correct(tiny_root, capsys, "tiny.chat")
    assert not ok, checks
