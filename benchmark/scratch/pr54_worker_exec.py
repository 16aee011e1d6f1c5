#!/usr/bin/env python3
"""``benchmark/run.py`` with one statement executed in the chip worker as
its loop function is entered (PR 54: to take a part of the chip watcher out
for one run, e.g. ``"from ray_tpu.perf import chipwatch as c;
c._watcher._source = None"`` for the host's half alone):

    python3 benchmark/scratch/pr54_worker_exec.py "<statement>" --workload ...

No option of the program selects any of this; a script, not a metric."""
import os
import runpy
import sys


def with_statement(train_loop, statement):
    def loop(config):
        exec(statement, {})
        return train_loop(config)

    return loop


def run_wrapped(wrap, argv) -> int:
    """``benchmark/run.py`` of the current directory with arguments
    ``argv``, the loop function it hands ``JaxTrainer`` replaced by
    ``wrap(loop)``."""
    sys.argv = ["benchmark/run.py", *argv]
    sys.path.insert(0, os.getcwd())
    import ray_tpu.train

    class Patched(ray_tpu.train.JaxTrainer):
        def __init__(self, loop, **kw):
            super().__init__(wrap(loop), **kw)

    ray_tpu.train.JaxTrainer = Patched  # run_train imports it when called
    run = runpy.run_path(os.path.join("benchmark", "run.py"), run_name="run")
    return run["main"]()


def main() -> int:
    statement = sys.argv[1]
    return run_wrapped(lambda loop: with_statement(loop, statement),
                       sys.argv[2:])


if __name__ == "__main__":
    sys.exit(main())
