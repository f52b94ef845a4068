"""BENCHMARK.json and the files it names.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric sits in a file of its own, found here by the name the
manifest gives: `configs/<config>.json` (the manifest's `file`),
`traffic/<traffic>.json`, `generators/<generator>.py`,
`drivers/<driver>.py`, `layer_metrics/<metric>.py`.  Adding a cell needs new
files and new manifest entries, and no edit to this file or to `run.py`.
"""

from __future__ import annotations

import importlib
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

# The driver's character rules (the contract in PERF.md section 2).
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH_RE = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}


class ManifestError(ValueError):
    pass


def _line(text, what):
    if not (isinstance(text, str) and 1 <= len(text) <= 200
            and "\n" not in text and "\t" not in text):
        raise ManifestError(f"{what}: 1 to 200 characters on one line")


def _name(text, what):
    if not (isinstance(text, str) and NAME_RE.match(text)):
        raise ManifestError(f"{what}: {text!r} is not a name")


def _keys(entry, required, optional, what):
    extra = set(entry) - set(required) - set(optional)
    missing = set(required) - set(entry)
    if extra or missing:
        raise ManifestError(f"{what}: extra keys {sorted(extra)}, "
                            f"missing {sorted(missing)}")


class Manifest:
    def __init__(self, data: dict, root: str = ROOT):
        self.data = data
        self.root = root
        self.validate()
        self.configs = {c["name"]: c for c in data["configs"]}
        self.cells = {w["name"]: w for w in data["workloads"]}
        self.end_to_end = {m["name"]: m for m in data["end_to_end"]}
        self.per_layer = {m["name"]: m for m in data["per_layer"]}

    # -- the rules the driver refuses a file over, before any run ----------

    def validate(self) -> None:
        d = self.data
        if set(d) != TOP_KEYS:
            raise ManifestError(f"top-level keys {sorted(d)}")
        if not (1 <= len(d["command"]) <= 32):
            raise ManifestError("command: 1 to 32 words")
        for word in d["command"]:
            _line(word, "command word")
            if word.startswith("/") or ".." in word.split("/"):
                raise ManifestError(f"command word {word!r} leaves the repo")
        if not (1 <= len(d["paths"]) <= 16):
            raise ManifestError("paths: 1 to 16 directories")
        for p in d["paths"]:
            if not PATH_RE.match(p) or p.startswith("/") or ".." in p:
                raise ManifestError(f"path {p!r}")
        if not (isinstance(d["run_seconds"], int)
                and 1 <= d["run_seconds"] <= 51):
            raise ManifestError("run_seconds: a whole number from 1 to 51")
        if not 1 <= len(d["configs"]) <= 24:
            raise ManifestError("configs: 1 to 24")
        if not 2 <= len(d["workloads"]) <= 24:
            raise ManifestError("workloads: 2 to 24")
        if not 1 <= len(d["end_to_end"]) <= 16:
            raise ManifestError("end_to_end: 1 to 16")
        if not 1 <= len(d["per_layer"]) <= 128:
            raise ManifestError("per_layer: 1 to 128")
        files = set()
        for c in d["configs"]:
            _keys(c, ("name", "source", "file", "reduced", "why"), (),
                  "config")
            _name(c["name"], "config name")
            _line(c["source"], "config source")
            _line(c["why"], "config why")
            if not any(c["file"].startswith(p + "/") for p in d["paths"]):
                raise ManifestError(f"{c['file']} is outside paths")
            if c["file"] in files:
                raise ManifestError(f"{c['file']} serves two configs")
            files.add(c["file"])
            if len(c["reduced"]) > 16:
                raise ManifestError("reduced: at most 16 keys")
            for key in c["reduced"]:
                _name(key, "reduced key")
        config_names = [c["name"] for c in d["configs"]]
        pairs = set()
        for w in d["workloads"]:
            _keys(w, ("name", "config", "traffic", "chips", "why"), (),
                  "workload")
            for key in ("name", "config", "traffic"):
                _name(w[key], f"workload {key}")
            _line(w["why"], "workload why")
            if w["chips"] not in (1, 4):
                raise ManifestError(f"{w['name']}: chips is 1 or 4")
            if w["config"] not in config_names:
                raise ManifestError(f"{w['name']}: unknown config")
            if (w["config"], w["traffic"]) in pairs:
                raise ManifestError(f"{w['name']}: pair appears twice")
            pairs.add((w["config"], w["traffic"]))
        used = {w["config"] for w in d["workloads"]}
        if used != set(config_names):
            raise ManifestError("every configuration is used by some cell")
        four = sum(w["chips"] == 4 for w in d["workloads"])
        if four > max(1, len(d["workloads"]) // 4):
            raise ManifestError("too many four-chip cells")
        cells = [w["name"] for w in d["workloads"]]
        e2e = {}
        for m in d["end_to_end"]:
            _keys(m, ("name", "unit", "better", "bound", "source"),
                  ("workloads",), "end_to_end metric")
            self._metric(m, cells)
            if m["source"] not in ("host_clock", "device_trace"):
                raise ManifestError(f"{m['name']}: source {m['source']}")
            if not 0.01 <= m["bound"] <= 0.1:
                raise ManifestError(f"{m['name']}: bound {m['bound']}")
            e2e[m["name"]] = set(m.get("workloads", cells))
        if e2e.get("setup_s") != set(cells):
            raise ManifestError("every cell reports setup_s")
        for m in d["per_layer"]:
            _keys(m, ("name", "unit", "better", "source", "layer", "moves"),
                  ("workloads",), "per_layer metric")
            self._metric(m, cells)
            _line(m["layer"], "layer")
            if m["moves"] not in e2e:
                raise ManifestError(f"{m['name']} moves an unknown metric")
            if not set(m.get("workloads", cells)) <= e2e[m["moves"]]:
                raise ManifestError(
                    f"{m['name']} is reported where {m['moves']} is not")
        names = [m["name"] for m in d["end_to_end"] + d["per_layer"]]
        for group in (names, cells, config_names):
            if len(set(group)) != len(group):
                raise ManifestError(f"a name appears twice in {group}")
        for cell in cells:
            if not any(cell in ws and n != "setup_s"
                       for n, ws in e2e.items()):
                raise ManifestError(f"{cell}: no end-to-end metric")
            if not any(cell in m.get("workloads", cells)
                       for m in d["per_layer"]):
                raise ManifestError(f"{cell}: no per-layer metric")

    @staticmethod
    def _metric(m, cells):
        _name(m["name"], "metric name")
        if not UNIT_RE.match(m["unit"]):
            raise ManifestError(f"{m['name']}: unit {m['unit']!r}")
        if m["better"] not in ("lower", "higher"):
            raise ManifestError(f"{m['name']}: better")
        if m["source"] not in SOURCES:
            raise ManifestError(f"{m['name']}: source {m['source']!r}")
        for cell in m.get("workloads", ()):
            if cell not in cells:
                raise ManifestError(f"{m['name']}: unknown cell {cell}")

    # -- look-ups -----------------------------------------------------------

    def metrics_of(self, cell: str, group: str) -> list:
        """Names of the `end_to_end` or `per_layer` metrics of `cell`."""
        return [m["name"] for m in self.data[group]
                if cell in m.get("workloads", self.cells)]

    def load_config(self, name: str) -> dict:
        with open(os.path.join(self.root, self.configs[name]["file"])) as f:
            return json.load(f)

    def load_traffic(self, name: str) -> dict:
        with open(os.path.join(self.root, self.data["paths"][0], "traffic",
                               name + ".json")) as f:
            return json.load(f)


def load(root: str = ROOT) -> Manifest:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return Manifest(json.load(f), root)


def module(kind: str, name: str):
    """`benchmark/<kind>/<name>.py`, for kind in generators, drivers,
    layer_metrics, reference."""
    _name(name, kind)
    return importlib.import_module(f"benchmark.{kind}.{name}")


def peaks(device_kind: str) -> dict:
    """The published peaks of `device_kind`; an unknown device raises."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["peaks"]
    kind = device_kind.lower()
    for key, row in table.items():
        if key in kind:
            return row
    raise KeyError(f"no peaks known for device_kind {device_kind!r}; "
                   f"add a row to benchmark/peaks.json with its source")


def fields(config: dict, rehearse: bool = False) -> dict:
    """A configuration file's constructor `fields` (its `rehearsal_fields`,
    at nano size, for a CPU rehearsal)."""
    return dict(config["rehearsal_fields" if rehearse else "fields"])


def model_config(config: dict, overrides: dict | None = None,
                 rehearse: bool = False):
    """The program's config object from a configuration file."""
    mod = importlib.import_module(config["module"])
    kwargs = fields(config, rehearse)
    kwargs.update(overrides or {})
    if isinstance(kwargs.get("dtype"), str):
        import jax.numpy as jnp
        kwargs["dtype"] = getattr(jnp, kwargs["dtype"])
    return getattr(mod, config["constructor"])(**kwargs)


def memory_report(stats: list) -> dict:
    """Device memory from each chip's `memory_stats()`, the fullest chip
    deciding.  On this runtime `peak_bytes_in_use` counts live buffers
    (state, weights, pools, batches) and leaves out the scratch a program
    reserves while it runs, which `peak_bytes_reserved` counts (it equals
    the compiler's `temp_size_in_bytes`).  A steady loop holds both at once,
    so the peak reported is the buffers in use now, at the window's end,
    plus the largest reservation: 15.1 GB for the train anchor, which is
    what the compiler predicts for its step."""
    def peak(s):
        return s.get("bytes_in_use", 0) + s.get("peak_bytes_reserved", 0)
    fullest = max(stats, key=peak) if stats else {}
    return {"memory_peak_bytes": int(peak(fullest)),
            "bytes_in_use": int(fullest.get("bytes_in_use", 0)),
            "peak_bytes_in_use": int(fullest.get("peak_bytes_in_use", 0)),
            "peak_bytes_reserved": int(fullest.get("peak_bytes_reserved", 0)),
            "bytes_limit": fullest.get("bytes_limit")}


def memory_line(m: dict) -> str:
    return (f"peak {m['memory_peak_bytes'] / 1e9:.2f} GB = in use at the "
            f"window's end {m['bytes_in_use'] / 1e9:.2f} + largest "
            f"reservation {m['peak_bytes_reserved'] / 1e9:.2f} (peak in use "
            f"{m['peak_bytes_in_use'] / 1e9:.2f}) of "
            f"{(m['bytes_limit'] or 0) / 1e9:.2f} GB")
