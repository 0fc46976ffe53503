"""The readers of the program's queue, device-wait and garbage-collection
spans, on synthetic contexts whose numbers are known."""

import pytest

from benchmark import run

READERS = ("serve.queued_p95_ms", "device.wait_share.edit",
           "device.wait_share.merge", "host.gc_share.edit",
           "host.gc_share.merge")


def ctx(spans=(), counters=None, seconds=10):
    return {"spans": list(spans), "counters": counters or {},
            "seconds": seconds, "hists": {}, "trace": None}


def queued(end, dur, method="applyChanges"):
    return ("serve.queued", end - dur, end, {"method": method})


@pytest.mark.parametrize("name", READERS)
def test_none_where_the_program_records_nothing(name):
    # what a tree without these spans and counters gives: the parent's
    assert run.reader(name)(ctx()) is None
    other = ctx([("device.kernel", 1.0, 2.0, {"rows": 8})],
                {("journal.fsyncs", ()): 3})
    assert run.reader(name)(other) is None


def test_queued_p95_is_the_exact_nearest_rank():
    read = run.reader("serve.queued_p95_ms")
    # 100 keystrokes queued 1..100 ms: rank ceil(95) is the 95th, 95 ms
    spans = [queued(1.0 + k / 100, (k + 1) / 1000) for k in range(100)]
    assert read(ctx(spans)) == pytest.approx(95.0)
    # 20 of them: rank 19, not interpolated and not a bucket bound
    got = read(ctx([queued(1.0, d / 1000) for d in range(101, 121)]))
    assert got == pytest.approx(119.0)


def test_queued_p95_keeps_keystrokes_that_end_in_the_window():
    read = run.reader("serve.queued_p95_ms")
    spans = [queued(1.0, 0.010), queued(2.0, 0.020),
             queued(3.0, 0.900, method="text"),  # not a keystroke
             queued(10.5, 0.800),  # dequeued after the close
             queued(-0.1, 0.700),  # dequeued before the window
             ("serve.drain", 0.0, 5.0, {"requests": 2})]
    assert read(ctx(spans)) == pytest.approx(20.0)
    assert read(ctx(spans[2:])) is None


@pytest.mark.parametrize("cell", ["edit", "merge"])
def test_device_wait_share_is_the_union_over_the_window(cell):
    read = run.reader(f"device.wait_share.{cell}")
    spans = [("device.wait", 1.0, 3.0, {}), ("device.wait", 2.0, 4.0, {}),
             ("device.wait", -1.0, 0.5, {}),  # clipped to 0.5 s
             ("device.wait", 9.5, 11.0, {}),  # clipped to 0.5 s
             ("device.readback", 0.0, 10.0, {})]
    assert read(ctx(spans)) == pytest.approx(100.0 * (3.0 + 0.5 + 0.5) / 10)


@pytest.mark.parametrize("cell", ["edit", "merge"])
def test_gc_share_from_spans_or_zero_from_the_counter(cell):
    read = run.reader(f"host.gc_share.{cell}")
    gen0 = {("host.gc_collections", (("generation", "0"),)): 41}
    spans = [("host.gc", 2.0, 2.2, {"generation": 2}),
             ("host.gc", 2.1, 2.3, {"generation": 1})]
    assert read(ctx(spans, gen0)) == pytest.approx(100.0 * 0.3 / 10)
    # collections counted, none left a span in the window: 0.0, not None
    assert read(ctx([], gen0)) == 0.0
    assert read(ctx([("host.gc", 11.0, 11.1, {})], gen0)) == 0.0
    # no collection counted and no span: nothing to read
    assert read(ctx([], {("host.gc_collections", (("generation", "0"),)):
                         0})) is None
