"""Every file the benchmark finds by name loads, and a run without a TPU
gives no result."""
import json
import os
import re
import subprocess
import sys

import pytest

import kinds
import run
from drive_actor_learner import network_kwargs

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    SPEC = json.load(_f)
CELLS = [w["name"] for w in SPEC["workloads"]]


def test_top_level_keys():
    """BENCHMARK.json has exactly the contract's keys."""
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"]
    assert os.path.exists(os.path.join(ROOT, SPEC["command"][1]))


def test_names():
    """Names are unique and made of the allowed characters."""
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in SPEC[group]]
        assert len(set(names)) == len(names)
        assert all(NAME.match(n) for n in names), names


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_load(cell):
    """A cell's configuration, traffic, limits and readers load."""
    spec = run.load_cell(cell)
    assert spec["config"]["name"] == spec["cell"]["config"]
    assert network_kwargs(spec["policy"])
    assert spec["limits"], "a cell compares at least one number"
    assert any(m["name"] == "setup_s" for m in spec["end_to_end"])
    for m in spec["per_layer"]:
        assert callable(run.load_reader(m["name"]))


def test_config_entries_point_at_their_files():
    """Each configuration entry names its own file."""
    for c in SPEC["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            body = json.load(f)
        assert body["name"] == c["name"]
        assert body["reduced"] == c["reduced"]


def test_per_layer_metrics_list_cells_that_report_what_they_move():
    """Per-layer metrics list only cells that report what they move."""
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    for m in SPEC["per_layer"]:
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", CELLS):
            assert cell in CELLS
            assert cell in moved.get("workloads", CELLS)


SEQ_DATA = os.path.join(BENCH, "tests", "data", "seq")
CONFIG_FILES = [(os.path.join(ROOT, c["file"]), kinds.HERE)
                for c in SPEC["configs"]] + [
    (os.path.join(SEQ_DATA, "configs", "seq_tiny.json"), SEQ_DATA)]


@pytest.mark.parametrize("path,data_dir", CONFIG_FILES,
                         ids=lambda p: os.path.basename(p))
def test_kind_file_exports_the_contract(path, data_dir):
    """Every configuration's kind file loads, exports the contract, and
    maps its weights to the program's tree and back leaf for leaf."""
    import jax

    with open(path) as f:
        config = json.load(f)
    kind = kinds.load(config["policy"]["kind"], data_dir)
    assert all(callable(getattr(kind, name)) for name in kinds.CONTRACT)
    w = jax.eval_shape(lambda k: kind.init_weights(k, config),
                       jax.random.PRNGKey(0))
    back = kind.from_program(kind.to_program(w, config), config)
    assert (jax.tree_util.tree_structure(back)
            == jax.tree_util.tree_structure(w))
    assert all(a is b for a, b in zip(jax.tree_util.tree_leaves(back),
                                      jax.tree_util.tree_leaves(w)))


def test_a_data_directory_brings_its_own_kind(tmp_path):
    """A kind under the data directory is found before the benchmark's;
    one that is missing, or exports less than the contract, is an
    error."""
    (tmp_path / "policies").mkdir()
    with open(os.path.join(BENCH, "policies", "mlp.py")) as f:
        body = f.read()
    (tmp_path / "policies" / "mlp.py").write_text(body + "\nOWN = True\n")
    assert kinds.load("mlp", str(tmp_path)).OWN
    assert not hasattr(kinds.load("mlp"), "OWN")
    (tmp_path / "policies" / "half.py").write_text(
        "def forward(weights, config, obs):\n    return obs\n")
    with pytest.raises(kinds.KindError, match="does not export"):
        kinds.load("half", str(tmp_path))
    with pytest.raises(kinds.KindError, match="no policies/none.py"):
        kinds.load("none", str(tmp_path))


def test_harness_names_no_kind_and_no_fixed_kernel_list():
    """Outside the kind files, the harness names no policy kind and no
    fixed list of kernels."""
    for name in sorted(os.listdir(BENCH)):
        if not name.endswith(".py"):
            continue
        with open(os.path.join(BENCH, name)) as f:
            text = f.read()
        for word in ('"conv"', '"mlp"', "kind ==", "KERNELS = ("):
            assert word not in text, (name, word)


@pytest.mark.parametrize("cell", CELLS)
def test_no_result_without_a_tpu(cell):
    """On the CPU a run exits non-zero and prints nothing."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell,
         "--seed", "3000000001", "--seconds", "2", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_unknown_device_kind_is_an_error(monkeypatch):
    """A chip missing from peaks.json is an error."""
    import jax

    class Fake:
        platform, device_kind = "tpu", "TPU v99"

    monkeypatch.setattr(jax, "devices", lambda *a: [Fake()])
    with pytest.raises(run.BenchError, match="no peaks"):
        run.find_devices(1)


def test_peaks_cover_the_chip_the_benchmark_runs_on():
    """The v5e peaks are the published ones."""
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)
    v5e = peaks["devices"]["TPU v5 lite"]
    assert v5e["int8_ops"] == 393e12 and v5e["bf16_flops"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
