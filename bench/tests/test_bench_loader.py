"""Every cell resolves to its files by name, names and units keep to their
alphabet, a new configuration, architecture, mix and metric dropped into a
fresh root are found with no edit to any file that is there, and a
configuration that names no architecture file, or one that is not there,
is refused."""

import json
import re

import pytest

from bench import loader

SPEC = loader.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_every_workload_resolves(cell):
    c = loader.load_cell(cell)
    assert c.config["name"] == next(w["config"] for w in SPEC["workloads"]
                                    if w["name"] == cell)
    assert c.traffic["arrivals"]["rate_per_s"] > 0
    assert {m["name"] for m in c.end_to_end} >= {"setup_s"}
    assert len(c.end_to_end) >= 2 and c.per_layer
    for m in c.per_layer:
        assert callable(c.readers[m["name"]].read)
    assert c.arch.__file__.endswith(f"archs/{c.config['arch']}.py")
    assert all(callable(getattr(c.arch, f)) for f in loader.ARCH_API)


def test_names_and_units_keep_to_their_alphabet():
    names = [w[k] for w in SPEC["workloads"]
             for k in ("name", "config", "traffic")]
    names += [c["name"] for c in SPEC["configs"]]
    names += [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    names += [k for c in SPEC["configs"] for k in c["reduced"]]
    assert all(NAME.match(n) for n in names), names
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", m["unit"]), m
        assert m["better"] in ("lower", "higher")
    with pytest.raises(loader.BenchError):
        loader.check_name("has space", "metric")
    with pytest.raises(loader.BenchError):
        loader.check_unit("tokens per second")


def test_files_match_the_benchmark():
    for c in SPEC["configs"]:
        data = json.loads((loader.ROOT / c["file"]).read_text())
        assert data["name"] == c["name"] and data["reduced"] == c["reduced"]
        assert data["source"] == c["source"]
    for m in SPEC["per_layer"]:
        assert (loader.BENCH / "metrics" / f"{m['name']}.py").is_file()
        moves = {e["name"] for e in SPEC["end_to_end"]}
        assert m["moves"] in moves


TOY_ARCH = """
def program_want(published, arch):
    return {}
def make(published, arch, key):
    return {}
def logits_at(published, arch, w, tokens, rows, rnd=None):
    return None
def dims(published):
    return published["layers"]
"""


def _toy_root(tmp_path, config: dict):
    bench = tmp_path / "bench"
    for d in ("configs", "traffic", "metrics", "archs"):
        (bench / d).mkdir(parents=True)
    (bench / "configs" / "toy.json").write_text(json.dumps(config))
    (bench / "archs" / "toy-arch.py").write_text(TOY_ARCH)
    (bench / "archs" / "broken.py").write_text("def make(): pass\n")
    (bench / "traffic" / "burst.json").write_text(
        json.dumps({"name": "burst", "arrivals": {"rate_per_s": 1.0}}))
    (bench / "metrics" / "toy.widget_ms.py").write_text(
        "def read(run):\n    return 1.5\n")
    spec = dict(SPEC)
    spec["workloads"] = [{"name": "toy.burst", "config": "toy",
                          "traffic": "burst", "chips": 1, "why": "test"}]
    spec["per_layer"] = [{"name": "toy.widget_ms", "unit": "ms",
                          "better": "lower", "source": "program_span",
                          "layer": "toy", "moves": "setup_s",
                          "workloads": ["toy.burst"]}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp_path


def test_new_config_mix_and_metric_are_found_by_name(tmp_path):
    root = _toy_root(tmp_path, {"name": "toy", "arch": "toy-arch"})
    cell = loader.load_cell("toy.burst", root=root)
    assert cell.config == {"name": "toy", "arch": "toy-arch"}
    assert cell.readers["toy.widget_ms"].read(None) == 1.5
    assert cell.arch.dims({"layers": 3}) == 3
    with pytest.raises(loader.BenchError):
        loader.load_cell("toy.missing", root=root)


@pytest.mark.parametrize("arch,match", [(None, "names no architecture"),
                                        ("absent", "no arch file"),
                                        ("broken", "defines no program_want"),
                                        ("../toy-arch", "not a valid name")])
def test_config_without_a_known_architecture_is_refused(tmp_path, arch,
                                                       match):
    config = {"name": "toy"} if arch is None else {"name": "toy",
                                                   "arch": arch}
    root = _toy_root(tmp_path, config)
    with pytest.raises(loader.BenchError, match=match):
        loader.load_cell("toy.burst", root=root)


def test_run_exits_2_on_a_config_without_an_architecture(tmp_path,
                                                         monkeypatch):
    from bench import run
    root = _toy_root(tmp_path, {"name": "toy"})
    real = loader.load_cell
    monkeypatch.setattr(loader, "load_cell",
                        lambda name: real(name, root=root))
    assert run.main(["--workload", "toy.burst", "--seed", "1",
                     "--seconds", "1"]) == 2
