#!/usr/bin/env python3
"""The train loop's final report of one run, kept as JSON (PR 41):

    python3 benchmark/scratch/final_report.py <out.json> --workload <cell> ...

Runs ``benchmark/run.py`` of the tree it is started in (the current
directory) with the arguments after ``<out.json>``, unchanged but for one
thing: what ``run_train`` returns under ``train`` (the loop's final report:
``reference``, ``compiles_at_warm`` / ``_at_end`` / ``_after_reference``,
``held_rows`` where the model counts them, the losses) is also written to
``<out.json>``. The result line does not carry the report, and two trees'
reports are how one sees that a change to the harness left them alike. A
script, not a metric."""
import json
import os
import runpy
import sys



def main() -> int:
    out, sys.argv = sys.argv[1], ["benchmark/run.py", *sys.argv[2:]]
    sys.path.insert(0, os.getcwd())
    run = runpy.run_path(os.path.join("benchmark", "run.py"), run_name="run")
    run_train = run["run_train"]

    def keeping(*args):
        result = run_train(*args)
        with open(out, "w") as f:
            json.dump(result["train"], f)
        return result

    run["main"].__globals__["run_train"] = keeping
    return run["main"]()


if __name__ == "__main__":
    sys.exit(main())
