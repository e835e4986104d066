"""The character rules of `BENCHMARK.json`'s names, units and texts,
and a check of the whole file against them and against the files the
harness finds by name."""
from __future__ import annotations

import json
import os
import re

NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}\Z")
UNIT = re.compile(r"[A-Za-z0-9_/%.-]{1,16}\Z")
PATH = re.compile(r"[A-Za-z0-9_./-]{1,200}\Z")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
ENTRY_KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def text_ok(s) -> bool:
    """1 to 200 characters on one line, no tab."""
    return (isinstance(s, str) and 1 <= len(s) <= 200
            and not any(c in s for c in "\n\r\t"))


def problems(doc: dict, root: str) -> list:
    """Every rule ``doc`` (a parsed `BENCHMARK.json` at ``root``)
    breaks; empty when it keeps them all."""
    out = []
    if set(doc) != TOP_KEYS:
        out.append(f"top-level keys {sorted(set(doc) ^ TOP_KEYS)}")
    for p in doc.get("paths", []):
        if not PATH.match(p) or p.startswith("/") or ".." in p.split("/"):
            out.append(f"path {p!r}")
    for word in doc.get("command", []):
        if not text_ok(word):
            out.append(f"command word {word!r}")
    for kind, keys in ENTRY_KEYS.items():
        seen = set()
        for e in doc.get(kind, []):
            extra = set(e) - keys - ({"workloads"} if kind in (
                "end_to_end", "per_layer") else set())
            if extra or not keys <= set(e):
                out.append(f"{kind} {e.get('name')!r} keys")
            if not NAME.match(str(e.get("name", ""))):
                out.append(f"{kind} name {e.get('name')!r}")
            if e.get("name") in seen:
                out.append(f"{kind} name {e['name']!r} twice")
            seen.add(e.get("name"))
            if "unit" in e and not UNIT.match(e["unit"]):
                out.append(f"unit {e['unit']!r}")
            if "better" in e and e["better"] not in ("lower", "higher"):
                out.append(f"better {e['better']!r}")
            for key in ("why", "layer", "source"):
                if key in e and not text_ok(e[key]):
                    out.append(f"{kind} {e['name']!r} {key}")
            for key in ("config", "traffic"):
                if key in e and not NAME.match(e[key]):
                    out.append(f"{kind} {e['name']!r} {key} {e[key]!r}")
            for key in e.get("reduced", []):
                if not NAME.match(key):
                    out.append(f"reduced key {key!r}")
    configs = {c["name"]: c for c in doc.get("configs", [])}
    for c in configs.values():
        if not os.path.isfile(os.path.join(root, c["file"])):
            out.append(f"config file {c['file']} missing")
    for w in doc.get("workloads", []):
        path = os.path.join(root, "bench", "workloads", f"{w['name']}.json")
        if not os.path.isfile(path):
            out.append(f"workload file {path} missing")
            continue
        with open(path) as f:
            wl = json.load(f)
        for key in ("config", "traffic", "chips"):
            if wl.get(key) != w.get(key):
                out.append(f"{w['name']}: {key} differs from its file")
        if w.get("config") not in configs:
            out.append(f"{w['name']}: unknown config {w.get('config')!r}")
        if not os.path.isfile(os.path.join(
                root, "bench", "traffic", f"{w.get('traffic')}.json")):
            out.append(f"{w['name']}: no traffic file")
    return out
