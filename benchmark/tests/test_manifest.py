import copy
import importlib
import json
import os
import shutil

import pytest

from benchmark import manifest as M


def test_manifest_loads_and_passes_the_drivers_rules():
    m = M.load()
    assert m.data["command"] == ["python3", "benchmark/run.py"]
    assert len(json.dumps(m.data)) < 64 * 1024
    for cell in m.cells:
        assert "setup_s" in m.metrics_of(cell, "end_to_end")
        assert len(m.metrics_of(cell, "end_to_end")) >= 2
        assert m.metrics_of(cell, "per_layer")


@pytest.mark.parametrize("edit, message", [
    (lambda d: d["workloads"][0].update(name="has space"), "not a name"),
    (lambda d: d["end_to_end"][0].update(unit="tokens per s"), "unit"),
    (lambda d: d["end_to_end"][0].update(bound=0.2), "bound"),
    (lambda d: d["per_layer"][0].update(why="x"), "extra keys"),
    (lambda d: d["workloads"][0].update(chips=2), "chips"),
    (lambda d: d.update(run_seconds=60), "run_seconds"),
    (lambda d: [w.update(chips=4) for w in d["workloads"][:2]],
     "four-chip"),
    (lambda d: d["per_layer"].append(dict(
        d["per_layer"][0], name="x", moves="serve_tokens_per_s",
        workloads=["train_gpt2s_1chip"])), "is reported where"),
])
def test_manifest_refuses_what_the_driver_refuses(edit, message):
    data = copy.deepcopy(M.load().data)
    edit(data)
    with pytest.raises(M.ManifestError, match=message):
        M.Manifest(data)


def test_every_file_the_manifest_names_exists_and_agrees():
    m = M.load()
    for cell in m.cells.values():
        config = m.load_config(cell["config"])
        assert config["source"] == m.configs[cell["config"]]["source"]
        assert config["reduced"] == m.configs[cell["config"]]["reduced"]
        traffic = m.load_traffic(cell["traffic"])
        assert hasattr(M.module("drivers", traffic["driver"]), "run")
        assert hasattr(M.module("generators", traffic["generator"]), "make")
        # published widths: every published size is the field it maps to
        for key, field in config["field_of"].items():
            assert config["published"][key] == config["fields"][field]
    # a per-layer metric's file is its reader; its declaration (layer, unit,
    # source, moves, cells) is the manifest's entry and sits nowhere else
    for name in m.per_layer:
        assert callable(M.module("layer_metrics", name).read), name


def test_peaks_table_raises_on_an_unknown_device():
    assert M.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError, match="no peaks known"):
        M.peaks("cpu")


def test_a_fifth_cell_needs_only_new_files_and_entries(tmp_path):
    """A new configuration with a reference of its own, a new traffic mix and
    a new per-layer metric: files of their own and manifest entries, no edit
    to run.py, to a driver or to any file that is there."""
    root = tmp_path / "repo"
    shutil.copytree(os.path.join(M.ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("out", "__pycache__",
                                                  "testdata"))
    before = {p: p.read_bytes() for p in (root / "benchmark").rglob("*")
              if p.is_file()}
    data = copy.deepcopy(M.load().data)

    config = json.loads((root / "benchmark/configs/gpt2-small.json")
                        .read_text())
    config["source"] = "https://huggingface.co/openai-community/gpt2-medium/blob/main/config.json"
    config["published"].update(n_layer=24, n_embd=1024, n_head=16)
    config["fields"].update(n_layers=24, d_model=1024, n_heads=16, d_ff=4096)
    config["reference_module"] = "gpt2_medium"
    (root / "benchmark/reference/gpt2_medium.py").write_text(
        "from benchmark.reference.gpt2 import *  # noqa: F401,F403\n"
        "FAMILY = 'another block would be written out here'\n")
    (root / "benchmark/configs/gpt2-medium.json").write_text(
        json.dumps(config))
    traffic = json.loads((root / "benchmark/traffic/decode_saturated.json")
                         .read_text())
    traffic["clients"] = 24
    traffic["requests"]["output_len"] = {"dist": "log_uniform", "lo": 8,
                                         "hi": 512}
    (root / "benchmark/traffic/decode_mixed.json").write_text(
        json.dumps(traffic))
    (root / "benchmark/layer_metrics/cut_requests.py").write_text(
'def read(run):\n    return run["notes"]["cut"]\n')

    data["configs"].append({
        "name": "gpt2-medium", "source": config["source"],
        "file": "benchmark/configs/gpt2-medium.json", "reduced": [],
        "why": "a third depth and width of the same block"})
    data["workloads"].append({
        "name": "serve_gpt2m_mixed", "config": "gpt2-medium",
        "traffic": "decode_mixed", "chips": 1, "why": "outputs 8-512"})
    for metric in data["end_to_end"] + data["per_layer"]:
        if metric["name"] in ("serve_tokens_per_s", "compile_s"):
            metric.setdefault("workloads", list(M.load().cells)) \
                if metric["name"] == "compile_s" else None
            if "workloads" in metric:
                metric["workloads"].append("serve_gpt2m_mixed")
    data["per_layer"].append({
        "name": "cut_requests", "unit": "requests", "better": "lower",
        "source": "host_clock", "layer": "serve",
        "moves": "serve_tokens_per_s", "workloads": ["serve_gpt2m_mixed"]})
    (root / "BENCHMARK.json").write_text(json.dumps(data))

    m = M.load(str(root))
    cell = m.cells["serve_gpt2m_mixed"]
    assert m.load_config(cell["config"])["fields"]["n_layers"] == 24
    mix = m.load_traffic(cell["traffic"])
    schedule = M.module("generators", mix["generator"]).make(
        mix, 3, 20.0, 50304)
    assert len(schedule) > 20
    assert "cut_requests" in m.metrics_of("serve_gpt2m_mixed", "per_layer")
    # the harness and both drivers find a module by the name the data gives
    # (`manifest.module`): the new reader and the new reference resolve
    packages = [importlib.import_module(f"benchmark.{kind}")
                for kind in ("layer_metrics", "reference")]
    for package in packages:
        package.__path__.append(str(root / "benchmark" /
                                    package.__name__.split(".")[-1]))
    try:
        reader = M.module("layer_metrics", "cut_requests")
        assert reader.read({"notes": {"cut": 3}}) == 3
        reference = M.module("reference", m.load_config(
            cell["config"])["reference_module"])
        assert reference.FAMILY and callable(reference.loss_and_grad)
    finally:
        for package in packages:
            package.__path__.pop()
    # and no driver, nor the replica, names a family of its own accord
    for path in ("drivers/train.py", "drivers/serve.py", "replica.py"):
        assert "gpt2" not in (root / "benchmark" / path).read_text(), path
    # nothing that was there has changed
    for path, content in before.items():
        assert path.read_bytes() == content, path
