"""The check fails what it must: the control (the reference computed in
fp8, below the configurations' bfloat16) and the faults a serving cell can
have, planted in the timed path underneath a whole run on the CPU."""
import json

import numpy as np
import pytest

from bench_paths import (BENCH_DIR, DATA, TINY_CELL,  # noqa: F401
                         tiny_bench, tiny_layout)

import control
from chipbench import check, faults, runner, serving, spec
from chipbench.load import Sent
from chipbench.traffic import Stream

REF = spec.load_module(BENCH_DIR / "reference", "dense_gqa")
TINY = json.loads((DATA / "configs" / "tiny-dense.json").read_text())
MIX = json.loads((DATA / "traffic" / "tiny_mix.json").read_text())
LIMIT = TINY["check"]["logit_gap_limit"]


def run(tiny_bench, tiny_layout, tmp_path):
    return runner.run(["--workload", TINY_CELL, "--seed", "31",
                       "--seconds", "1.5", "--trace", "0"],
                      layout=tiny_layout, bench=tiny_bench, need_chip=False,
                      persistent_cache=False, trace_dir=tmp_path)


@pytest.mark.parametrize("fault",
                         sorted(set(faults.FAULTS) - faults.MULTI_REPLICA))
def test_a_fault_in_the_timed_path_is_not_correct(fault, monkeypatch,
                                                  tiny_bench, tiny_layout,
                                                  tmp_path):
    faults.FAULTS[fault](monkeypatch.setattr)
    line = run(tiny_bench, tiny_layout, tmp_path)
    assert line["correct"] is False
    gap, limit = line["checks"]["logit_gap"]
    assert gap > limit


def served_synchronously(seed, n=24):
    """The tiny cell's first ``n`` requests, served by one engine in its
    synchronous loop, so the sample does not depend on timing."""
    import jax
    params = REF.program_params(TINY["model"], seed)
    _, rs = serving.build(TINY, [params], jax.devices()[:1], traced=False)
    eng = rs.engines[0]
    stream = Stream(MIX, seed, TINY["model"]["vocab_size"],
                    TINY["deployment"]["max_seq"])
    sent = []
    for k in range(n):
        prompt, new = stream.request(k)
        r = eng.submit_request(prompt, max_new_tokens=new)
        sent.append(Sent(index=k, client=0, due=0.0, prompt=prompt,
                         max_new=new, request=r))
    eng.run_until_idle()
    return sent


class Served:
    """What ``check.checks`` reads of a run: the requests due in it, and
    the one chip (and replica) that served them."""
    chips = 1

    def __init__(self, sent):
        self.sent = sent

    def due_in_window(self):
        return self.sent


@pytest.mark.parametrize("seed", (3, 4, 5))
def test_the_control_fails_where_the_program_passes(seed):
    """Through the harness's own check and limit, the fp8 control is not
    correct where the program is."""
    sent = served_synchronously(seed)
    every = sum(len(s.request.generated) for s in sent)     # all sampled
    judged = check.checks(Served(sent), REF, TINY, seed, every,
                          modes=("f32", "fp8"))
    assert check.compare(judged["f32"]), judged["f32"]
    assert not check.compare(judged["fp8"]), judged["fp8"]
    assert judged["f32"]["logit_gap"]["value"] <= LIMIT \
        < judged["fp8"]["logit_gap"]["value"]


def test_control_readings_through_a_whole_window(tiny_bench, tiny_layout):
    """``control.py`` serves a window as a run does and judges the program
    and the control with the check: correct, and not correct."""
    import jax
    cell = spec.resolve(tiny_bench, TINY_CELL, tiny_layout)
    rows = control.readings(cell, [3], 1.5, jax.devices(),
                            layout=tiny_layout, log=lambda _m: None)
    assert rows[0]["program_correct"] is True
    assert rows[0]["control_correct"] is False
    assert rows[0]["program"] <= LIMIT < rows[0]["control"]


def test_gaps_by_hand():
    logits = np.array([[1.0, 3.0, 2.0], [0.5, 0.0, 0.25]])
    assert np.allclose(check.gaps(logits, [1, 2]), [0.0, 0.25])
