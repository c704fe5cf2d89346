"""BENCHMARK.json against the contract's shape, and every name it gives
resolves to the files of its own."""
import re

import spec
import traffic

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys_and_limits(benchmark_json):
    b = benchmark_json
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["paths"] == ["bench"] and 1 <= b["run_seconds"] <= 51
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert set(e2e) == {"train_tokens_per_s", "setup_s"}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


def test_names_units_and_keys(benchmark_json):
    b = benchmark_json
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [x["name"] for x in b[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names), names
    for m in b["end_to_end"] + b["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    moves = {m["name"] for m in b["end_to_end"]}
    for m in b["per_layer"]:
        assert m["moves"] in moves and "\n" not in m["layer"]
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}


def test_every_cell_resolves_its_files(benchmark_json):
    b = benchmark_json
    for w in b["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        cfg = spec.config(b, w["config"])
        tr = spec.traffic(w["traffic"])
        limits = spec.limits(w["name"])
        assert limits and set(limits) <= {"loss_gap", "grad_gap",
                                          "change_gap"}
        assert spec.driver(tr["kind"]).run
        traffic.TokenFeed.from_traffic(tr, cfg["vocab_size"], 1)
        assert cfg["reduced"] == next(c["reduced"] for c in b["configs"]
                                      if c["name"] == w["config"])
        for m in spec.metrics_of(b, w["name"], "per_layer"):
            assert callable(spec.reader(m["name"]).read)
    for c in b["configs"]:
        assert c["file"].startswith("bench/configs/")
        assert any(w["config"] == c["name"] for w in b["workloads"])


def test_feed_is_a_function_of_seed_and_step():
    tr = {"batch": 2, "seq": 2048, "mix": {"ramp": 1, "markov": 1}}
    big = 2 ** 31 + 977
    a = traffic.TokenFeed.from_traffic(tr, 50277, big)
    b = traffic.TokenFeed.from_traffic(tr, 50277, big)
    assert (a.batch(3) == b.batch(3)).all()
    assert not (a.batch(3) == a.batch(4)).all()
    other = traffic.TokenFeed.from_traffic(tr, 50277, big + 1)
    assert not (a.batch(3) == other.batch(3)).all()
    assert a.batch(0).max() < 50277 and a.batch(0).dtype.name == "int32"
