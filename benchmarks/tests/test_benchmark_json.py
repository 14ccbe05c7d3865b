"""BENCHMARK.json and the files it names say the same thing."""

import os

import run as R


def test_every_name_has_its_file():
    bench = R.read_json(R.ROOT, "BENCHMARK.json")
    for c in bench["configs"]:
        cfg = R.read_json(R.ROOT, c["file"])
        assert cfg["name"] == c["name"]
        assert set(c["reduced"]) == set(cfg["reduced_why"])
    for w in bench["workloads"]:
        assert os.path.exists(os.path.join(R.HERE, "configs",
                                           w["config"] + ".json"))
        mix = R.read_json(R.HERE, "traffic", w["traffic"] + ".json")
        own = os.path.join(R.HERE, "cells", w["name"] + ".json")
        if os.path.exists(own):
            mix.update(R.read_json(own))
        assert mix["loop"] == "open" and mix["rate_scale"] > 0
        assert all(op["target_throughput"] > 0 and "body" in op
                   and "spec" in op for op in mix["operations"])
        e2e, layer = R.cell_metrics(bench, w["name"])
        assert {"setup_s"} < {m["name"] for m in e2e}
        assert layer
    for m in bench["per_layer"]:
        mod = R.load_reader(m["name"])
        assert (mod.NAME, mod.UNIT, mod.BETTER, mod.SOURCE, mod.LAYER,
                mod.MOVES) == (m["name"], m["unit"], m["better"],
                               m["source"], m["layer"], m["moves"])
        assert callable(mod.read)
