"""The readers of the OpLog splice's counters, on synthetic contexts whose
numbers are known."""

import pytest

from benchmark import run

READERS = ("staging.splice_moved_rows.edit",
           "staging.splice_moved_rows.merge")


def ctx(counters):
    return {"spans": [], "counters": counters, "seconds": 20, "hists": {},
            "trace": None}


@pytest.mark.parametrize("name", READERS)
def test_none_where_the_program_counts_no_splice(name):
    # what a tree without these counters gives: the parent's
    read = run.reader(name)
    assert read(ctx({})) is None
    assert read(ctx({("oplog.append_rows", ()): 12})) is None


@pytest.mark.parametrize("name", READERS)
def test_rows_moved_a_splice_over_every_mode(name):
    read = run.reader(name)
    got = read(ctx({
        ("oplog.splices", (("mode", "suffix"),)): 3,
        ("oplog.splices", (("mode", "tail"),)): 4,
        ("oplog.splices", (("mode", "remap"),)): 1,
        ("oplog.splice_rows_moved", ()): 200,
    }))
    assert got == pytest.approx(25.0)
    # tail splices alone move nothing, and the window's delta omits a
    # counter that did not change
    assert read(ctx({("oplog.splices", (("mode", "tail"),)): 9})) == 0.0


def test_reads_the_program_counters():
    from automerge_tpu.ops.oplog import count_splice

    c0 = run.counters()
    count_splice(False, False, 7)
    count_splice(False, True, 0)
    delta = run.count_delta(c0, run.counters())
    assert run.reader("staging.splice_moved_rows.edit")(ctx(delta)) == 3.5
