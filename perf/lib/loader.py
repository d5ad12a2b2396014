"""Find everything a run needs by the names in BENCHMARK.json.

A cell, a configuration, a per-layer metric, a runner and a reference are
each a file of their own; nothing here names one of them. Adding one is
adding its file and its entry in BENCHMARK.json.
"""
import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


class BenchmarkError(Exception):
    """A name that BENCHMARK.json or a data file does not bear out."""


def _read_json(path):
    if not os.path.isfile(path):
        raise BenchmarkError(f"missing file {path}")
    with open(path) as f:
        return json.load(f)


def load_module(path, name):
    """Import one file by path (file names may hold `-` and `.`)."""
    if not os.path.isfile(path):
        raise BenchmarkError(f"missing file {path}")
    spec = importlib.util.spec_from_file_location(
        "perf_" + "".join(c if c.isalnum() else "_" for c in name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _by_name(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise BenchmarkError(
        f"unknown {what} {name!r}; BENCHMARK.json has "
        f"{sorted(e['name'] for e in entries)}")


class Benchmark:
    """BENCHMARK.json and the files under its first path."""

    def __init__(self, root=ROOT):
        self.root = root
        self.doc = _read_json(os.path.join(root, "BENCHMARK.json"))
        self.dir = os.path.join(root, self.doc["paths"][0])

    def path(self, *parts):
        return os.path.join(self.dir, *parts)

    def cell(self, name):
        """The cell's entry merged over its own file."""
        entry = _by_name(self.doc["workloads"], name, "workload")
        data = _read_json(self.path("workloads", name + ".json"))
        for key in ("config", "chips"):
            if data.get(key) != entry[key]:
                raise BenchmarkError(
                    f"{name}: {key} is {data.get(key)!r} in its file and "
                    f"{entry[key]!r} in BENCHMARK.json")
        return data

    def config(self, name):
        entry = _by_name(self.doc["configs"], name, "config")
        return _read_json(os.path.join(self.root, entry["file"]))

    def runner(self, name):
        return load_module(self.path("runners", name + ".py"), name)

    def reference(self, config_name):
        return load_module(self.path("references", config_name + ".py"),
                           config_name)

    def peaks(self, device_kind):
        table = _read_json(self.path("lib", "peaks.json"))
        if device_kind not in table:
            raise BenchmarkError(
                f"no peaks recorded for device_kind {device_kind!r}; add it "
                f"to perf/lib/peaks.json with its source")
        return table[device_kind]

    def _reports(self, metric, cell_name, e2e_names):
        cells = metric.get("workloads")
        if cells is not None:
            return cell_name in cells
        return metric.get("moves", metric["name"]) in e2e_names

    def end_to_end(self, cell_name):
        """Names of the end-to-end metrics this cell reports."""
        return [m["name"] for m in self.doc["end_to_end"]
                if m.get("workloads") is None
                or cell_name in m["workloads"]]

    def per_layer(self, cell_name):
        """(entry, reader description) of each per-layer metric the cell
        reports."""
        e2e = set(self.end_to_end(cell_name))
        out = []
        for m in self.doc["per_layer"]:
            if self._reports(m, cell_name, e2e):
                out.append((m, _read_json(
                    self.path("layer_metrics", m["name"] + ".json"))))
        return out

    def read_layer_metric(self, entry, desc, facts):
        """One per-layer value, or None where there was nothing to read."""
        kind = desc.get("reader")
        if kind == "histogram":
            h = facts["histograms"].get(desc["histogram"])
            if not h or not h.get("count"):
                return None
            return float(h[desc["stat"]]) * float(desc.get("scale", 1.0))
        if kind == "python":
            # its own <metric>.py, or the file it names: metrics that split
            # one quantity by the end-to-end metric they move share a reader
            name = desc.get("file", entry["name"] + ".py")
            return load_module(self.path("layer_metrics", name),
                               name).read(facts)
        raise BenchmarkError(
            f"{entry['name']}: unknown reader {kind!r} in its file")

    def check_files(self):
        """Every file BENCHMARK.json names, directly or by convention."""
        missing = []
        for c in self.doc["configs"]:
            cfg_path = os.path.join(self.root, c["file"])
            paths = [cfg_path,
                     self.path("references", c["name"] + ".py")]
            if os.path.isfile(cfg_path):
                paths.append(self.path(
                    "runners", _read_json(cfg_path)["runner"] + ".py"))
            missing += [p for p in paths if not os.path.isfile(p)]
        for w in self.doc["workloads"]:
            p = self.path("workloads", w["name"] + ".json")
            if not os.path.isfile(p):
                missing.append(p)
        for m in self.doc["per_layer"]:
            p = self.path("layer_metrics", m["name"] + ".json")
            if not os.path.isfile(p):
                missing.append(p)
            elif _read_json(p).get("reader") == "python":
                code = self.path("layer_metrics", _read_json(p).get(
                    "file", m["name"] + ".py"))
                if not os.path.isfile(code):
                    missing.append(code)
        if missing:
            raise BenchmarkError("missing files: " + ", ".join(missing))
