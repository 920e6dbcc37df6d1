"""A cell whose configuration, traffic mix and metric reader exist only as
new files is found and loaded by name: nothing that exists is edited."""
import json
import os
import shutil

from bench import harness, registry

import tiny


def test_new_cell_is_found_by_name(tmp_path):
    root = tmp_path
    bench = root / "bench"
    for sub in ("configs", "traffic", "metrics"):
        (bench / sub).mkdir(parents=True)
    # the general system modules are shared; the rest of the cell is new
    shutil.copytree(os.path.join(tiny.ROOT, "bench", "systems"),
                    bench / "systems")
    base = json.load(open(os.path.join(tiny.ROOT, "bench", "configs",
                                       "xsbench-large.json")))
    (bench / "configs" / "xsbench-tiny.json").write_text(
        json.dumps({**base, **tiny.xsbench_config(), "name": "xsbench-tiny"}))
    (bench / "traffic" / "xsbench-event-tiny.json").write_text(
        json.dumps({"mode": "event", **tiny.XSBENCH_TRAFFIC}))
    (bench / "metrics" / "lookups_counted.py").write_text(
        "def read(ctx):\n    return ctx['stats']['lookups']\n")
    spec = {
        "configs": [{"name": "xsbench-tiny", "source": "test",
                     "file": "bench/configs/xsbench-tiny.json",
                     "reduced": [], "why": "test"}],
        "workloads": [{"name": "xs-new", "config": "xsbench-tiny",
                       "traffic": "xsbench-event-tiny", "chips": 1,
                       "why": "test"}],
        "end_to_end": [{"name": "xs_lookups_per_s", "unit": "lookups/s",
                        "better": "higher", "bound": 0.05,
                        "source": "host_clock"},
                       {"name": "setup_s", "unit": "s", "better": "lower",
                        "bound": 0.25, "source": "host_clock"}],
        "per_layer": [{"name": "lookups_counted", "unit": "lookups",
                       "better": "higher", "source": "program_counter",
                       "layer": "region", "moves": "xs_lookups_per_s"}]}
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = registry.Cell(registry.load_spec(str(root)), "xs-new", str(root))
    assert cell.config["n_isotopes"] == 8
    assert cell.traffic["block_lanes"] == 1024
    assert [m["name"] for m in cell.per_layer] == ["lookups_counted"]
    assert cell.reader("lookups_counted").read(
        {"stats": {"lookups": 7}}) == 7

    line = harness.run("xs-new", 11, 0.2, False, require_tpu=False,
                       root=str(root))
    assert line["correct"] is True
    assert set(line["metrics"]) == {"xs_lookups_per_s", "setup_s"}


def test_benchmark_json_names_existing_files():
    spec = registry.load_spec()
    for w in spec["workloads"]:
        cell = registry.Cell(spec, w["name"])
        assert cell.end_to_end and cell.per_layer
        for m in cell.per_layer:
            assert callable(cell.reader(m["name"]).read)
