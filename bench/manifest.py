"""Checks of ``BENCHMARK.json`` against the benchmark's own rules: every
name resolves to its files, names and units keep to their characters, and
every cell reports what each of its metrics moves."""
from __future__ import annotations

import json
import re
from pathlib import Path

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
TOP = {"command", "paths", "run_seconds", "configs", "workloads",
       "end_to_end", "per_layer"}


def _line(s) -> bool:
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def check(m: dict, root: Path) -> list[str]:
    """Every problem found; an empty list when there is none."""
    root = Path(root)
    bad: list[str] = []
    if set(m) != TOP:
        bad.append(f"top-level keys {sorted(m)}")
    if not (isinstance(m.get("run_seconds"), int)
            and 1 <= m["run_seconds"] <= 51):
        bad.append("run_seconds")
    names: set[str] = set()

    def name(kind, n):
        if not (isinstance(n, str) and NAME.match(n)):
            bad.append(f"{kind} name {n!r}")
        if n in names:
            bad.append(f"duplicate name {n!r}")
        names.add(n)

    confs = {}
    for c in m["configs"]:
        name("config", c["name"])
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            bad.append(f"config {c['name']} keys {sorted(c)}")
        if not (_line(c["source"]) and _line(c["why"])):
            bad.append(f"config {c['name']} source or why")
        f = root / c["file"]
        if not f.is_file():
            bad.append(f"config file {c['file']}")
            continue
        body = json.loads(f.read_text())
        if body.get("name") != c["name"] or body.get("source") != c["source"]:
            bad.append(f"config {c['name']}: name or source differ in its file")
        if sorted(body.get("reduced", [])) != sorted(c["reduced"]):
            bad.append(f"config {c['name']}: reduced differs from its file")
        for k in c["reduced"]:
            if not NAME.match(k):
                bad.append(f"reduced key {k!r}")
        if not (f.parent / f"{body['family']}.py").is_file():
            bad.append(f"config {c['name']}: no reference {body['family']}.py")
        confs[c["name"]] = body
    e2e = {x["name"]: x for x in m["end_to_end"]}
    cells = {}
    pairs = set()
    for w in m["workloads"]:
        name("workload", w["name"])
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            bad.append(f"workload {w['name']} keys {sorted(w)}")
        if w["config"] not in confs:
            bad.append(f"workload {w['name']}: no config {w['config']}")
        if not NAME.match(w["traffic"]) or not (
                root / "bench" / "traffic" / f"{w['traffic']}.json").is_file():
            bad.append(f"workload {w['name']}: no traffic {w['traffic']}")
        if w["chips"] not in (1, 4) or not _line(w["why"]):
            bad.append(f"workload {w['name']}: chips or why")
        if (w["config"], w["traffic"]) in pairs:
            bad.append(f"workload {w['name']}: pair repeated")
        pairs.add((w["config"], w["traffic"]))
        cells[w["name"]] = set()
    for x in m["end_to_end"]:
        name("metric", x["name"])
        if not x.get("source") in ("host_clock", "device_trace"):
            bad.append(f"{x['name']}: source")
        if not 0.01 <= x.get("bound", -1) <= 0.25:
            bad.append(f"{x['name']}: bound")
        for w in x.get("workloads", cells):
            if w not in cells:
                bad.append(f"{x['name']}: no workload {w}")
            else:
                cells[w].add(x["name"])
    if "setup_s" not in e2e or "workloads" in e2e["setup_s"]:
        bad.append("setup_s must be reported by every cell")
    layered = {w: 0 for w in cells}
    for x in m["end_to_end"] + m["per_layer"]:
        if not UNIT.match(x.get("unit", "")) or x.get("better") not in (
                "lower", "higher") or x.get("source") not in SOURCES:
            bad.append(f"{x['name']}: unit, better or source")
    for x in m["per_layer"]:
        name("metric", x["name"])
        if x.get("moves") not in e2e or not _line(x.get("layer")):
            bad.append(f"{x['name']}: moves or layer")
        for w in x.get("workloads", [c for c in cells
                                     if x.get("moves") in cells[c]]):
            if w not in cells or x.get("moves") not in cells[w]:
                bad.append(f"{x['name']}: {w} does not report {x.get('moves')}")
            else:
                layered[w] += 1
        if not (root / "bench" / "metrics" / f"{x['name']}.py").is_file():
            bad.append(f"{x['name']}: no reader")
    for w, got in cells.items():
        if len(got - {"setup_s"}) < 1 or not layered[w]:
            bad.append(f"{w}: needs an end-to-end metric besides setup_s "
                       f"and a per-layer metric")
    return bad
