"""The harness finds every cell's configuration, traffic, deployment kind,
traffic writer and metrics by name, and the file keeps to the contract's
form."""

import json
import os
import re

import pytest

from benchmark import deploy, run, traffic
from benchmark.tests.test_harness import run_cell, small_spec

ROOT = run.ROOT
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_cell_resolves_by_name(cell):
    spec = run.load_cell(ROOT, cell)
    dep = deploy.kind(spec["config"]["kind"]).Deployment
    assert callable(dep.compare) and callable(dep.apply)
    writer = traffic.writer(spec["traffic"]["writer"])
    assert callable(writer.plan)
    assert callable(getattr(writer, "warm", run.warm_growth))
    names = [m["name"] for m in spec["end_to_end"]]
    assert "setup_s" in names and len(names) >= 2
    assert spec["per_layer"], "every cell reports a per-layer metric"
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert callable(run.reader(m["name"]))


def test_names_units_and_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and os.path.isfile(
            os.path.join(ROOT, c["file"]))
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and len(w["why"]) <= 200
    layers = {}
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    moves = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in moves
        layers.setdefault(m["layer"], m["layer"])
        for w in m.get("workloads", []):
            e2e = [e["name"] for e in run.load_cell(ROOT, w)["end_to_end"]]
            assert m["moves"] in e2e


def test_a_new_metric_file_is_found_by_name(tmp_path, monkeypatch):
    (tmp_path / "metrics").mkdir()
    (tmp_path / "metrics" / "new.metric_x.py").write_text(
        "def read(ctx):\n    return ctx['seconds'] * 2\n")
    monkeypatch.setattr(run, "HERE", str(tmp_path))
    assert run.reader("new.metric_x")({"seconds": 3}) == 6


def test_a_reader_that_finds_nothing_returns_none():
    ctx = {"trace": None, "hists": {}, "counters": {}, "spans": [],
           "seconds": 1}
    for name in ("device.idle_share.edit", "resolve_roofline.merge",
                 "serve.queue_wait_p95_ms", "journal.fsync_share",
                 "staging.host_share.edit"):
        assert run.reader(name)(ctx) is None


TOY_KIND = '''
from benchmark import load, synth

text = load({kinds!r}, "text")


class Deployment(text.Deployment):
    def compare(self, client, srv, doc):
        served = client.call("text", doc=doc.handle, obj=synth.TEXT_OBJ)
        return {{"toy_text": int(served != doc.ref.text())}}
'''

TOY_WRITER = '''
from benchmark import deploy, load

plan = load({writers!r}, "keystroke").plan


def warm(client, dep, plan, cfg, meter):
    """Each document's window requests, one at a time, on a scratch copy."""
    n = 0
    for d, reqs, _ in plan.growth:
        doc = dep.docs[d]
        h = client.call("openDurable", name=doc.name + "-toy",
                        device=cfg["device"], fsync=cfg["fsync"])["doc"]
        raws = [doc.base_raw,
                b"".join(ch.raw for ch in plan.warm if ch.doc == d)]
        for raw in raws + [b"".join(ch.raw for ch in r) for r in reqs]:
            client.call("applyChanges", doc=h, data=deploy.b64(raw))
            n += 1
        client.call("free", doc=h)
    with open({marker!r}, "w") as f:
        f.write(str(n))
    return n
'''


def test_a_new_kind_and_writer_are_found_by_name(tmp_path, monkeypatch,
                                                 capsys):
    """A deployment of its own kind, writer, warm-up and state check runs
    through ``run.main`` from new files alone."""
    marker = tmp_path / "warmed"
    for folder, name, text in (
            ("kinds", "toy", TOY_KIND.format(kinds=deploy.KINDS_DIR)),
            ("writers", "toy", TOY_WRITER.format(
                writers=traffic.WRITERS_DIR, marker=str(marker)))):
        (tmp_path / folder).mkdir()
        (tmp_path / folder / (name + ".py")).write_text(text)
    monkeypatch.setattr(deploy, "KINDS_DIR", str(tmp_path / "kinds"))
    monkeypatch.setattr(traffic, "WRITERS_DIR", str(tmp_path / "writers"))

    def no_growth(*_):
        raise AssertionError("warm_growth ran in place of the writer's warm")

    monkeypatch.setattr(run, "warm_growth", no_growth)
    spec = small_spec()
    spec["config"]["kind"] = spec["traffic"]["writer"] = "toy"
    line = run_cell(capsys, spec)
    assert int(marker.read_text()) > 2
    assert line["checks"]["toy_text_docs_differing"] == {"value": 0,
                                                        "limit": 0}
    assert not [k for k in line["checks"] if k.startswith("rpc_")]
    assert line["correct"] is True


@pytest.mark.parametrize("part,key,folder", [
    ("config", "kind", "kinds"), ("traffic", "writer", "writers")])
def test_an_unknown_name_exits_2_naming_its_file(capsys, part, key, folder):
    spec = small_spec()
    spec[part][key] = "no_such_name"
    rc = run.main(["--workload", "collab.keystroke", "--seed", "1",
                   "--seconds", "1", "--trace", "0"], require_tpu=False,
                  spec=spec)
    out = capsys.readouterr()
    assert rc == 2 and out.out.strip() == ""
    assert os.path.join("benchmark", folder, "no_such_name.py") in out.err
