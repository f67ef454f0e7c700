"""Everything the harness knows about a cell, it finds by name.

``BENCHMARK.json`` (at the root of the checkout) names the cells, the
configurations and the metrics. Each name leads to a file of its own::

    benchmark/cells/<cell>.json            config, traffic, chips, runner
    benchmark/configs/<config>.json        the sizes as run
    benchmark/traffic/<mix>.json           generator kind + parameters
    benchmark/layer_metrics/<name>.json    layer, unit, moves, reader

so a later PR adds a cell, a mix, a configuration or a per-layer metric
by adding files and one entry in ``BENCHMARK.json``, and edits nothing.
"""

import importlib.util
import json
import os
import re

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CONFIG_GROUPS = ("source", "reduced", "reduced_why", "assumed", "deployment", "engine",
                 "trainer", "reference")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class SpecError(ValueError):
    pass


def _load(path):
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"{os.path.relpath(path, ROOT)} does not exist") from None
    except json.JSONDecodeError as e:
        raise SpecError(f"{os.path.relpath(path, ROOT)} is not JSON: {e}") from None


def _name(value, what):
    if not isinstance(value, str) or not NAME.match(value):
        raise SpecError(f"{what} {value!r} is not a name ([A-Za-z0-9_.-], at most 64)")
    return value


def _require(obj, keys, what):
    missing = [k for k in keys if k not in obj]
    if missing:
        raise SpecError(f"{what} lacks {missing}")


class Benchmark:
    """``BENCHMARK.json`` and the files it leads to, read from
    ``root`` (the checkout; tests pass a temporary copy)."""

    def __init__(self, root=ROOT):
        self.root = root
        self.doc = _load(os.path.join(root, "BENCHMARK.json"))
        _require(self.doc, ("command", "paths", "run_seconds", "configs", "workloads",
                            "end_to_end", "per_layer"), "BENCHMARK.json")
        self.dir = os.path.join(root, self.doc["paths"][0])
        self.run_seconds = int(self.doc["run_seconds"])
        self.workloads = {_name(w["name"], "workload"): w for w in self.doc["workloads"]}
        self.configs = {_name(c["name"], "config"): c for c in self.doc["configs"]}
        self.end_to_end = {_name(m["name"], "metric"): m for m in self.doc["end_to_end"]}
        self.per_layer = {_name(m["name"], "metric"): m for m in self.doc["per_layer"]}

    def path(self, *parts):
        return os.path.join(self.dir, *parts)

    def load(self, folder, name, attr):
        """``benchmark/<folder>/<name>.py`` → its ``attr``: how generator
        kinds, runners and readers are found. Loaded from this
        benchmark's own directory, so a file added there is enough."""
        path = self.path(folder, f"{_name(name, folder)}.py")
        if not os.path.isfile(path):
            raise SpecError(f"{os.path.relpath(path, self.root)} does not exist")
        key = f"_benchmark_{folder}_{name}_{abs(hash(path))}"
        spec = importlib.util.spec_from_file_location(key, path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        if not hasattr(module, attr):
            raise SpecError(f"{os.path.relpath(path, self.root)} has no {attr}()")
        return getattr(module, attr)

    def reader(self, metric):
        """``readers.<file>:<function>`` of a per-layer metric → the function."""
        module, _, attr = self.layer_metric(metric)["reader"].partition(":")
        folder, _, name = module.partition(".")
        if folder != "readers" or not attr:
            raise SpecError(f"layer metric {metric}: reader {module!r} is not readers.<file>:<fn>")
        return self.load("readers", name, attr)

    def cell(self, name):
        if name not in self.workloads:
            raise SpecError(f"no workload {name!r} in BENCHMARK.json "
                            f"(it has {sorted(self.workloads)})")
        entry = self.workloads[name]
        cell = _load(self.path("cells", f"{name}.json"))
        _require(cell, ("config", "traffic", "chips", "runner", "why"), f"cell {name}")
        for key in ("config", "traffic", "chips"):
            if cell[key] != entry[key]:
                raise SpecError(f"cell {name}: {key} is {cell[key]!r} in its file and "
                                f"{entry[key]!r} in BENCHMARK.json")
        _name(cell["config"], "config")
        _name(cell["traffic"], "traffic")
        _name(cell["runner"], "runner")
        if cell["chips"] not in (1, 4):
            raise SpecError(f"cell {name}: chips is {cell['chips']!r}, not 1 or 4")
        return cell

    def config(self, name):
        if name not in self.configs:
            raise SpecError(f"no config {name!r} in BENCHMARK.json")
        entry = self.configs[name]
        config = _load(os.path.join(self.root, entry["file"]))
        _require(config, ("source", "reduced", "assumed", "deployment", "reference"),
                 f"config {name}")
        if config["source"] != entry["source"]:
            raise SpecError(f"config {name}: source differs between its file and BENCHMARK.json")
        # the published config.json's keys sit at the top level of the file, as
        # the source has them; the groups the benchmark adds are named here
        config["model"] = {k: v for k, v in config.items() if k not in CONFIG_GROUPS}
        if sorted(config["reduced"]) != sorted(entry["reduced"]):
            raise SpecError(f"config {name}: reduced is {sorted(config['reduced'])} in its file "
                            f"and {sorted(entry['reduced'])} in BENCHMARK.json")
        return config

    def traffic(self, name):
        mix = _load(self.path("traffic", f"{_name(name, 'traffic')}.json"))
        _require(mix, ("kind",), f"traffic {name}")
        _name(mix["kind"], "generator kind")
        return mix

    def metrics_of(self, cell_name, which):
        """The metric entries of ``which`` (``end_to_end`` | ``per_layer``)
        that the cell reports: every one without a ``workloads`` key and
        every one that lists the cell."""
        table = self.end_to_end if which == "end_to_end" else self.per_layer
        return {n: m for n, m in table.items()
                if "workloads" not in m or cell_name in m["workloads"]}

    def layer_metric(self, name):
        """The per-layer metric's own file, checked against its entry."""
        entry = self.per_layer[name]
        spec = _load(self.path("layer_metrics", f"{name}.json"))
        _require(spec, ("layer", "unit", "moves", "source", "reader"), f"layer metric {name}")
        for key in ("layer", "unit", "moves", "source"):
            if spec[key] != entry[key]:
                raise SpecError(f"layer metric {name}: {key} is {spec[key]!r} in its file and "
                                f"{entry[key]!r} in BENCHMARK.json")
        if sorted(spec.get("cells", ())) != sorted(entry.get("workloads", ())):
            raise SpecError(f"layer metric {name}: cells {spec.get('cells')} in its file and "
                            f"workloads {entry.get('workloads')} in BENCHMARK.json")
        return spec

    def validate(self):
        """Every file the harness finds by name, against the contract's
        limits on names, units and sources. → the number of files read."""
        read = 0
        for table in (self.end_to_end, self.per_layer):
            for name, m in table.items():
                if not UNIT.match(m.get("unit", "")):
                    raise SpecError(f"metric {name}: unit {m.get('unit')!r}")
                if m.get("source") not in SOURCES:
                    raise SpecError(f"metric {name}: source {m.get('source')!r}")
                if m.get("better") not in ("lower", "higher"):
                    raise SpecError(f"metric {name}: better {m.get('better')!r}")
                for w in m.get("workloads", ()):
                    if w not in self.workloads:
                        raise SpecError(f"metric {name} lists unknown workload {w!r}")
        if "setup_s" not in self.end_to_end:
            raise SpecError("end_to_end lacks setup_s")
        for name, m in self.end_to_end.items():
            if m["source"] not in ("host_clock", "device_trace"):
                raise SpecError(f"end-to-end metric {name}: source {m['source']!r}")
            if not 0 < m.get("bound", 0) <= 0.1:
                raise SpecError(f"end-to-end metric {name}: bound {m.get('bound')!r}")
        for name in self.configs:
            self.config(name)
            read += 1
        used = set()
        for name in self.workloads:
            cell = self.cell(name)
            self.load("generators", self.traffic(cell["traffic"])["kind"], "generate")
            self.load("runners", cell["runner"], "run")
            used.add(cell["config"])
            read += 2
            e2e = self.metrics_of(name, "end_to_end")
            if "setup_s" not in e2e or len(e2e) < 2:
                raise SpecError(f"cell {name} reports {sorted(e2e)}: setup_s and one more")
            layer = self.metrics_of(name, "per_layer")
            if not layer:
                raise SpecError(f"cell {name} reports no per-layer metric")
            for lname, m in layer.items():
                if m["moves"] not in e2e:
                    raise SpecError(f"layer metric {lname} moves {m['moves']!r}, which cell "
                                    f"{name} does not report")
        if used != set(self.configs):
            raise SpecError(f"configs without a cell: {sorted(set(self.configs) - used)}")
        for name in self.per_layer:
            self.reader(name)
            read += 1
        return read
