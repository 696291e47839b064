"""BENCHMARK.json against its contract, and the harness found by names."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

import manifest as M
import published_widths

ROOT, BENCH = M.ROOT, M.BENCH_DIR
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def man():
    return M.load_manifest()


def test_top_level_keys_and_limits(man):
    assert set(man) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert man["paths"] == ["benchmarks", "tests/benchmark"]
    assert 1 <= man["run_seconds"] <= 51 and isinstance(man["run_seconds"], int)
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    # a full check of 24 cells fits the driver's 43200 s
    assert (2 + 14 * 24) * (man["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200
    assert sum(w["chips"] == 4 for w in man["workloads"]) <= 1


@pytest.mark.parametrize("section", ["configs", "workloads", "end_to_end", "per_layer"])
def test_names_units_and_keys(man, section):
    allowed = {"configs": {"name", "source", "file", "reduced", "why"},
               "workloads": {"name", "config", "traffic", "chips", "why"},
               "end_to_end": {"name", "unit", "better", "bound", "source", "workloads"},
               "per_layer": {"name", "unit", "better", "source", "layer", "moves", "workloads"}}[section]
    names = [e["name"] for e in man[section]]
    assert len(names) == len(set(names))
    for e in man[section]:
        assert set(e) <= allowed and allowed - {"workloads"} <= set(e), e
        assert NAME.match(e["name"]), e["name"]
        for key in ("why", "layer", "source"):
            if key in e:
                assert 1 <= len(e[key]) <= 200 and "\n" not in e[key] and "\t" not in e[key]
        if "unit" in e:
            assert UNIT.match(e["unit"]) and e["better"] in ("lower", "higher")
            assert e["source"] in SOURCES
    if section == "end_to_end":
        assert "setup_s" in names
        for e in man[section]:
            assert e["source"] in ("host_clock", "device_trace") and 0.01 <= e["bound"] <= 0.1


def test_every_cell_finds_its_files(man):
    for w in man["workloads"]:
        cell = M.find_cell(man, w["name"])
        assert NAME.match(w["config"]) and NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        entry = cell["config_entry"]
        assert entry["file"].startswith("benchmarks/configs/")
        cfg = cell["config_values"]
        assert cfg["source"] == entry["source"] and cfg["reduced"] == entry["reduced"]
        for key in entry["reduced"]:
            assert NAME.match(key) and not re.search(r"_dim$|_rank$|hidden_size|intermediate|head", key)
        M.load_driver(cell["traffic_values"]["driver"])
        names = {m["name"] for m in cell["end_to_end"]}
        assert "setup_s" in names and len(names) >= 2 and cell["per_layer"]
    used = {w["config"] for w in man["workloads"]}
    assert used == {c["name"] for c in man["configs"]}


def _configs(man, root=ROOT):
    for entry in man["configs"]:
        with open(os.path.join(root, entry["file"])) as f:
            yield entry, json.load(f)


def test_published_widths_are_untouched(man):
    """Every configuration against the public values of its own architecture
    (``data/published/<model_type>.json``): a key the public file has and
    ``reduced`` does not list is equal to it; ``reduced`` holds depth, experts
    held and vocabulary only, each with its public value under ``published``;
    the floors of the model-configs guide where they apply. A configuration
    whose ``model_type`` has no public-values file fails."""
    checked = 0
    for entry, cfg in _configs(man):
        published_widths.check(cfg, entry, published_widths.load_public(cfg["model_type"]))
        checked += 1
    assert checked == len(man["configs"]) >= 1


def _alter(case: str, cfg: dict, entry: dict, public: dict):
    """One fault a configuration's PR could bring; each has to fail the check."""
    roles, values = public["roles"], public["values"]
    depth = roles["depth"]
    if case == "width_altered":
        width = next(k for k in values if published_widths.WIDTH.search(k) and isinstance(values[k], int))
        cfg[width] += 1
    elif case == "width_left_out":
        del cfg[next(k for k in values if published_widths.WIDTH.search(k))]
    elif case == "reduced_key_lacks_its_public_value":
        del cfg["published"][entry["reduced"][0]]
    elif case == "reduced_key_states_another_public_value":
        cfg["published"][entry["reduced"][0]] += 1
    elif case == "reduced_names_a_width":
        width = next(k for k in values if published_widths.WIDTH.search(k) and isinstance(values[k], int))
        for e in (cfg, entry):
            e["reduced"] = e["reduced"] + [width]
        cfg["published"][width], cfg[width] = values[width], values[width] // 2
    elif case == "reduced_names_a_key_that_is_no_cut_of_scale":
        for e in (cfg, entry):
            e["reduced"] = e["reduced"] + ["rope_theta"]
        cfg["published"]["rope_theta"] = values["rope_theta"]
    elif case == "depth_under_the_floor":
        cfg[depth] = roles["leading_dense_layers"] + max(4, roles["layer_period"]) - 1
    elif case == "vocabulary_under_an_eighth":
        vocab = roles["vocabulary"]
        for e in (cfg, entry):
            e["reduced"] = sorted(set(e["reduced"]) | {vocab})
        cfg["published"][vocab], cfg[vocab] = values[vocab], values[vocab] // 8 - 1
    elif case == "source_is_another_model":
        cfg["source"] = entry["source"] = public["source"] + "x"
    else:
        raise ValueError(case)


ALTERED = ["width_altered", "width_left_out", "reduced_key_lacks_its_public_value",
           "reduced_key_states_another_public_value", "reduced_names_a_width",
           "reduced_names_a_key_that_is_no_cut_of_scale", "depth_under_the_floor", "vocabulary_under_an_eighth",
           "source_is_another_model"]


def _architectures(man):
    """(entry, configuration, public values) of one configuration of each
    architecture: the manifest's, and the toy one the seam's test adds."""
    seen = {}
    for entry, cfg in _configs(man):
        seen.setdefault(cfg["model_type"], (entry, cfg, published_widths.load_public(cfg["model_type"])))
    toy = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "toy_arch")
    with open(os.path.join(toy, "config.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(toy, "published.json")) as f:
        seen["toy"] = ({"source": cfg["source"], "reduced": cfg["reduced"]}, cfg, json.load(f))
    return list(seen.values())


@pytest.mark.parametrize("case", ALTERED)
def test_an_altered_configuration_fails_the_published_widths(man, case):
    for entry, cfg, public in _architectures(man):
        published_widths.check(cfg, entry, public)  # sound as committed
        cfg, entry = json.loads(json.dumps(cfg)), dict(entry)
        _alter(case, cfg, entry, public)
        with pytest.raises(AssertionError):
            published_widths.check(cfg, entry, public)


def test_a_model_type_without_public_values_fails():
    with pytest.raises(AssertionError, match="no public values"):
        published_widths.load_public("an-architecture-nobody-published")


def test_per_layer_metrics_move_what_their_cells_report(man):
    e2e = {m["name"]: m for m in man["end_to_end"]}
    cells = {w["name"] for w in man["workloads"]}
    for m in man["per_layer"]:
        reader = M.load_metric_reader(m["name"])
        assert (reader.LAYER, reader.UNIT, reader.MOVES, reader.SOURCE) == (
            m["layer"], m["unit"], m["moves"], m["source"]), m["name"]
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        target = e2e[m["moves"]]
        for w in m["workloads"]:
            assert w in cells and ("workloads" not in target or w in target["workloads"]), (m["name"], w)
        # nothing to read -> nothing returned
        assert reader.read(None, None, {}, {"chips": 1, "peaks": {}}) is None
    for w in cells:
        assert any(w in m["workloads"] for m in man["per_layer"])


def test_a_later_pr_adds_files_and_entries_only(tmp_path):
    """A configuration, a traffic mix, a driver and a per-layer metric, each a
    new file plus a manifest entry, in a copy of the benchmark: the harness
    finds them by name and runs the new cell with no edit to a file that was
    there."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "benchmarks", ignore=shutil.ignore_patterns("__pycache__"))
    man = M.load_manifest()
    (root / "benchmarks/configs/toy.json").write_text(json.dumps({"source": "https://example.org/toy", "x": 3}))
    (root / "benchmarks/traffic/ticks.json").write_text(json.dumps({"driver": "count_ticks", "ticks": 7}))
    (root / "benchmarks/drivers/count_ticks.py").write_text(
        "def run(ctx):\n"
        "    ctx.setup_done()\n"
        "    n = ctx.traffic['ticks'] * ctx.settings['x']\n"
        "    return {'values': {'ticks_per_s': n / ctx.seconds}, 'counters': {'ticks': n}, 'attempted': n,\n"
        "            'failed': 0, 'correct': True, 'memory_peak_bytes': 0}\n")
    (root / "benchmarks/metrics/ticks_seen.py").write_text(
        "LAYER, UNIT, MOVES, SOURCE = 'toy', 'count', 'ticks_per_s', 'program_counter'\n"
        "def read(trace, spans, counters, cell):\n    return counters.get('ticks')\n")
    man["configs"].append({"name": "toy", "source": "https://example.org/toy", "file": "benchmarks/configs/toy.json",
                           "reduced": [], "why": "test"})
    man["workloads"].append({"name": "toy_ticks", "config": "toy", "traffic": "ticks", "chips": 1, "why": "test"})
    man["end_to_end"].append({"name": "ticks_per_s", "unit": "1/s", "better": "higher", "bound": 0.01,
                              "source": "host_clock", "workloads": ["toy_ticks"]})
    man["per_layer"].append({"name": "ticks_seen", "unit": "count", "better": "higher", "source": "program_counter",
                             "layer": "toy", "moves": "ticks_per_s", "workloads": ["toy_ticks"]})
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    cell = M.find_cell(M.load_manifest(str(root)), "toy_ticks", str(root))
    assert cell["config_values"]["x"] == 3 and cell["traffic_values"]["ticks"] == 7
    assert [m["name"] for m in cell["per_layer"]] == ["ticks_seen"]
    assert M.load_driver("count_ticks", cell["bench_dir"]).run
    assert M.load_metric_reader("ticks_seen", cell["bench_dir"]).read(None, None, {"ticks": 21}, cell) == 21
    # ... and the copy holds only BENCHMARK.json and the files under paths:
    # no program to import, so a run fails and prints no result line
    proc = subprocess.run([sys.executable, str(root / "benchmarks/run.py"), "--workload", man["workloads"][0]["name"],
                           "--seed", "1", "--seconds", "1", "--trace", "0", "--cpu-rehearsal"],
                          capture_output=True, text=True, cwd=str(root),
                          env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"})
    assert proc.returncode != 0 and '"correct"' not in proc.stdout
