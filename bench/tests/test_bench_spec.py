"""``BENCHMARK.json`` keeps the declaration's rules, and every name in it
has its file."""
import json
import re

import pytest

from bench.lib import spec
from bench.tests.conftest import REPO

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH_RE = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


def one_line(text) -> bool:
    """1 to 200 characters on one line, no tab."""
    return isinstance(text, str) and 0 < len(text) <= 200 and not re.search(r"[\n\r\t]", text)


def rel_path(text) -> bool:
    """A relative path inside the checkout."""
    return (
        isinstance(text, str)
        and bool(PATH_RE.match(text))
        and not text.startswith("/")
        and ".." not in text.split("/")
    )


def problems(raw: dict) -> list[str]:
    """Where ``raw`` breaks the character and shape rules of the
    benchmark's declaration; empty when it keeps them."""
    out = []

    def need(cond, what):
        if not cond:
            out.append(what)

    need(isinstance(raw.get("command"), list) and 0 < len(raw["command"]) <= 32,
         "command: 1 to 32 words")
    for word in raw.get("command", []):
        need(one_line(word) and not word.startswith("/") and ".." not in word.split("/"),
             f"command word {word!r}")
    need(0 < len(raw.get("paths", [])) <= 16, "paths: 1 to 16")
    for p in raw.get("paths", []):
        need(rel_path(p), f"path {p!r}")
    rs = raw.get("run_seconds")
    need(isinstance(rs, int) and 1 <= rs <= 51, "run_seconds: whole number 1..51")
    names = set()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in raw.get(group, []):
            need(isinstance(e.get("name"), str) and bool(NAME_RE.match(e["name"])),
                 f"{group} name {e.get('name')!r}")
            key = (group in ("end_to_end", "per_layer"), group == "configs", e.get("name"))
            need(key not in names, f"duplicate name {e.get('name')!r}")
            names.add(key)
    for c in raw.get("configs", []):
        need(set(c) == {"name", "source", "file", "reduced", "why"}, f"config keys {sorted(c)}")
        need(one_line(c.get("source")) and one_line(c.get("why")), f"config {c.get('name')} text")
        need(rel_path(c.get("file")), f"config file {c.get('file')!r}")
        need(len(c.get("reduced", [])) <= 16, "reduced: at most 16 keys")
        for k in c.get("reduced", []):
            need(bool(NAME_RE.match(k)), f"reduced key {k!r}")
    pairs = set()
    for w in raw.get("workloads", []):
        need(set(w) == {"name", "config", "traffic", "chips", "why"}, f"workload keys {sorted(w)}")
        need(w.get("chips") in (1, 4), f"chips of {w.get('name')}")
        need(one_line(w.get("why")), f"why of {w.get('name')}")
        for k in ("config", "traffic"):
            need(bool(NAME_RE.match(str(w.get(k)))), f"{k} of {w.get('name')}")
        pair = (w.get("config"), w.get("traffic"))
        need(pair not in pairs, f"pair {pair} twice")
        pairs.add(pair)
    for group, extra in (("end_to_end", {"bound"}), ("per_layer", {"layer", "moves"})):
        for m in raw.get(group, []):
            base = {"name", "unit", "better", "source"} | extra
            need(set(m) in (base, base | {"workloads"}), f"metric keys {sorted(m)}")
            need(bool(UNIT_RE.match(str(m.get("unit")))), f"unit {m.get('unit')!r}")
            need(m.get("better") in ("lower", "higher"), f"better of {m.get('name')}")
            need(m.get("source") in SOURCES, f"source of {m.get('name')}")
            if "layer" in m:
                need(one_line(m["layer"]), f"layer of {m.get('name')}")
    return out

RAW = json.loads((REPO / "BENCHMARK.json").read_text())


def test_declaration_keeps_the_rules():
    assert problems(RAW) == []
    assert set(RAW) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}


@pytest.mark.parametrize("bad, what", [
    ({"name": "a b"}, "name"),
    ({"name": "a,b"}, "name"),
    ({"name": "µs"}, "name"),
    ({"unit": "tokens per second"}, "unit"),
    ({"better": "up"}, "better"),
    ({"source": "stopwatch"}, "source"),
    ({"why": "x"}, "keys"),
])
def test_a_broken_metric_is_found(bad, what):
    raw = json.loads(json.dumps(RAW))
    raw["end_to_end"][0].update(bad)
    assert any(what in p for p in problems(raw)), problems(raw)


def test_paths_and_command_stay_inside_the_checkout():
    for bad in (["/abs"], ["../out"], ["a b"]):
        raw = dict(RAW, paths=bad)
        assert problems(raw)
    assert problems(dict(RAW, command=["python3", "/opt/run.py"]))


def test_every_name_has_its_file():
    s = spec.load(REPO)
    for c in RAW["configs"]:
        cfg = s.config(c["name"])
        assert cfg["name"] == c["name"]
        for key in c["reduced"]:
            assert key in cfg and key in cfg["reduced"]
            assert not key.endswith(("_dim", "_rank")) and key != "dim"
    for w in RAW["workloads"]:
        mix = s.traffic(w["traffic"])
        assert mix["rate_per_s"] > 0 and mix["k"] > 0
    for m in RAW["end_to_end"] + RAW["per_layer"]:
        assert callable(s.reader(m["name"]))


def test_every_cell_reports_setup_another_end_to_end_metric_and_a_layer():
    s = spec.load(REPO)
    for w in RAW["workloads"]:
        e2e = {m["name"] for m in s.metrics_for(w["name"], "end_to_end")}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert s.metrics_for(w["name"], "per_layer")


def test_layers_are_named_alike():
    names = {m["layer"] for m in RAW["per_layer"]}
    assert all(one_line(n) for n in names)
    moved = {m["name"] for m in RAW["end_to_end"]}
    assert all(m["moves"] in moved for m in RAW["per_layer"])
