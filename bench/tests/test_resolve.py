"""The harness finds every cell's files by name alone."""
import json

import pytest

import harness

BENCH = harness.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_resolves_to_its_files(name):
    cell = harness.resolve(name)
    assert cell.config["kernels"]
    assert cell.config["check"]["gap_eps_limit"] > 0
    assert any(m["name"] == "setup_s" for m in cell.end_to_end)
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in cell.per_layer:
        assert callable(harness.load_reader(m["name"]))
    harness.accelerator(cell.config)          # builds without a search


CONFIG_FILES = sorted((harness.BENCH / "configs").glob("*.json"))


def test_config_entries_name_their_files():
    for c in BENCH["configs"]:
        cfg = json.loads((harness.REPO / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        assert c["file"].startswith(tuple(p + "/" for p in BENCH["paths"]))


@pytest.mark.parametrize("path", CONFIG_FILES, ids=lambda p: p.stem)
def test_configs_keep_the_published_shapes(path):
    from repro.core.workloads import BY_NAME

    cfg = json.loads(path.read_text())
    for k in cfg["kernels"]:
        w = BY_NAME[k["name"]]
        assert (k["m"], k["k"], k["n"], k["d_mk"], k["d_kn"]) == (
            w.m, w.k, w.n, w.d_mk, w.d_kn)


def test_config_accelerator_is_the_searched_design():
    """The clusters copied as data equal what the DSE search gives."""
    from repro.core import dse

    searched = dse.aespa_opt()
    for path in CONFIG_FILES:
        assert harness.accelerator(json.loads(path.read_text())) == searched


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_finds_its_arrival_process(name):
    import arrivals

    kind = arrivals.kind_module(harness.resolve(name).traffic["kind"])
    for fn in ("validate", "plan", "warmup", "drive"):
        assert callable(getattr(kind, fn))


def test_unknown_cell_is_refused():
    with pytest.raises(KeyError):
        harness.resolve("no.such.cell")
