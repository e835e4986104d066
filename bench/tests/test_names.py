"""BENCHMARK.json keeps the character rules, and every entry finds its
files."""
import copy
import json
import os

import pytest

from bench.names import NAME, UNIT, problems

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def doc():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_benchmark_json_keeps_the_rules():
    assert problems(doc(), ROOT) == []


@pytest.mark.parametrize("name,ok", [
    ("paper_edge.fig5", True), ("device_us_per_event.single", True),
    ("_x-1", True), ("9lives", True), ("a" * 64, True), ("a" * 65, False),
    ("has space", False), ("a,b", False), ("a/b", False), (".hidden", False),
    ("µs", False), ("", False)])
def test_name_characters(name, ok):
    assert bool(NAME.match(name)) is ok


@pytest.mark.parametrize("unit,ok", [
    ("req/s", True), ("%", True), ("us/event", True), ("s", True),
    ("tokens per second", False), ("µs", False), ("", False),
    ("a" * 17, False)])
def test_unit_characters(unit, ok):
    assert bool(UNIT.match(unit)) is ok


@pytest.mark.parametrize("edit,expect", [
    (lambda d: d["end_to_end"][0].update(unit="req per s"), "unit"),
    (lambda d: d["per_layer"][0].update(why="extra"), "keys"),
    (lambda d: d["workloads"][0].update(name="bad name"), "name"),
    (lambda d: d["workloads"].append(dict(d["workloads"][0])), "twice"),
    (lambda d: d["configs"][0].update(why="two\nlines"), "why"),
    (lambda d: d["paths"].append("../outside"), "path"),
    (lambda d: d["workloads"][0].update(traffic="no_such_mix"), "traffic"),
])
def test_problems_are_found(edit, expect):
    d = copy.deepcopy(doc())
    edit(d)
    assert any(expect in p for p in problems(d, ROOT))
