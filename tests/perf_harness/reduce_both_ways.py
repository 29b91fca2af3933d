#!/usr/bin/env python3
"""One traced run of a cell whose trace is reduced BOTH ways (run on the
chip; PR 51 made it to show that the rooflines did not move):

  chiprun -- python3 tests/perf_harness/reduce_both_ways.py \\
      chiprun_out/pr51 --workload big.train --seed 7 --seconds 20 --trace 1

The run is benchmark/run.py's own, with its arguments. Beside the result
line it writes <out>/<cell>.json: the kernels' seconds by FAMILY (what the
run reports), by the exact names the files held until PR 51 as the parent
added them up (`names_oracle.py`) and as the new reducer does, and every
roofline of the cell by family and by name.
"""

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [ROOT, HERE]

from benchmark import manifest, run, trace_reduce  # noqa: E402
import names_oracle  # noqa: E402


def main(out_dir, argv):
    reduce_trace, layer_metrics = trace_reduce.reduce_trace, run.layer_metrics

    def both(path, kernels=()):
        trace = reduce_trace(path, kernels)
        if trace:
            names = names_oracle.names_of(kernels)
            trace["by_name_parent"] = names_oracle.kernel_s_by_name(
                path, names)
            trace["by_name"] = reduce_trace(path, names)["kernel_s"]
        return trace

    def record(cell, obs):
        metrics = layer_metrics(cell, obs)
        trace = obs.get("trace")
        if trace and "by_name_parent" in trace:
            rooflines = {}
            for m in cell.per_layer:
                args = manifest.load_layer_metric(m["name"],
                                                  cell.root)["args"]
                if "kernels" in args:
                    rooflines[m["name"]] = {
                        "by_family": metrics.get(m["name"], {}).get("value"),
                        "by_name_parent": names_oracle.roofline_by_name(
                            dict(obs, trace={
                                "kernel_s": trace["by_name_parent"]}), args)}
            os.makedirs(out_dir, exist_ok=True)
            with open(os.path.join(out_dir, f"{cell.name}.json"), "w") as fh:
                json.dump({"cell": cell.name,
                           "by_family": trace["kernel_s"],
                           "by_name_parent": trace["by_name_parent"],
                           "by_name": trace["by_name"],
                           "rooflines": rooflines,
                           "busy_s": trace["busy_s"],
                           "window_s": trace["window_s"]}, fh, indent=1)
        return metrics

    trace_reduce.reduce_trace, run.layer_metrics = both, record
    return run.main(argv)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1], sys.argv[2:]))
