"""A whole run on the CPU with the timed path broken underneath: each fault
a cell can have turns ``correct`` false.  (The cells run on one chip, so
there is no exchange between chips to leave out.)"""
import pytest

from bench.tests.runs import tiny_run


def _drop_writes(monkeypatch):
    # a step that returns its state unchanged: DML acknowledged, not applied
    from repro.core.lsm import LSMStore

    def insert(self, row):
        with self._lock:
            return self._next_ts_locked()

    monkeypatch.setattr(LSMStore, "insert", insert)
    monkeypatch.setattr(LSMStore, "delete", insert)


def _half_batch(monkeypatch):
    # half of the blocks left out of the kernel's input, the rest summed
    from repro.core import pushdown
    orig = pushdown.stage_device

    def stage(store, plan):
        st = orig(store, plan)
        if st is not None:
            st.counts[1::2] = 0
        return st

    monkeypatch.setattr(pushdown, "stage_device", stage)


def _altered_answer(monkeypatch):
    # one aggregate altered where the answer is produced
    from repro.core import pushdown
    orig = pushdown.emit_device_groups

    def emit(q, plan, stage, g_cnt, g_sums, *a, **k):
        g_sums = g_sums.copy()
        g_sums[0, :] *= 1.001
        return orig(q, plan, stage, g_cnt, g_sums, *a, **k)

    monkeypatch.setattr(pushdown, "emit_device_groups", emit)


@pytest.mark.parametrize("fault,workload,number", [
    (_drop_writes, "tiny-rf", "lost_writes"),
    (_half_batch, "tiny-closed", "wrong_answers"),
    (_altered_answer, "tiny-closed", "max_rel_err"),
])
def test_fault_makes_the_run_incorrect(monkeypatch, tmp_path, fault,
                                       workload, number):
    fault(monkeypatch)
    out = tiny_run(monkeypatch, tmp_path, workload)
    assert out["correct"] is False
    c = out["checks"][number]
    assert c["value"] > c["limit"]
