#!/usr/bin/env python3
"""What ``libtpu.sdk.tpumonitoring`` answers inside the process that holds
the chip while a cell's step runs (PR 54, the first call on the chip):

    python3 benchmark/scratch/tpumon_probe.py <out dir> --workload <cell> ...

Runs ``benchmark/run.py`` of the tree it is started in with the arguments
after ``<out dir>``, unchanged but for one thing: the loop function the
chip worker is handed first starts one daemon thread a supported metric,
each of which calls ``get_metric(<name>)`` ten times a second and writes
what it got (``description()`` once, ``data()`` and the seconds the call
took every time) to ``<out dir>/<metric>.jsonl``, and one more thread that
writes the host's side once a second to ``<out dir>/host.jsonl``: the
process's and the loop thread's CPU clocks, ``getrusage``, the load, and
every task of ``/proc/self/task`` by name with its CPU ticks. A metric
whose call hangs hangs its own thread only. ``summary.json`` (written by
this script after the run) says, a metric, how many calls returned, how
long the slowest took and how many distinct ``data()`` it saw: which
counters are live and change within a second. A script, not a metric."""
import json
import os
import sys


def probing(train_loop, out_dir):
    def loop(config):
        import resource
        import threading
        import time

        os.makedirs(out_dir, exist_ok=True)
        loop_thread = threading.get_ident()
        stop = threading.Event()

        def metric(name):
            from libtpu.sdk import tpumonitoring as mon

            with open(os.path.join(out_dir, name + ".jsonl"), "w") as f:
                described = False
                while not stop.is_set():
                    t, t0 = time.time(), time.perf_counter()
                    try:
                        m = mon.get_metric(name)
                        row = {"t": t, "data": list(m.data())}
                        if not described:
                            row["description"] = m.description()
                            described = True
                    except Exception as e:  # noqa: BLE001 - a probe
                        row = {"t": t, "error": repr(e)}
                    row["took"] = time.perf_counter() - t0
                    f.write(json.dumps(row, default=repr) + "\n")
                    f.flush()
                    stop.wait(0.1)

        def host():
            clock = time.pthread_getcpuclockid(loop_thread)
            with open(os.path.join(out_dir, "host.jsonl"), "w") as f:
                while not stop.is_set():
                    ru = resource.getrusage(resource.RUSAGE_SELF)
                    row = {"t": time.time(),
                           "process_cpu": time.process_time(),
                           "loop_cpu": time.clock_gettime(clock),
                           "nvcsw": ru.ru_nvcsw, "nivcsw": ru.ru_nivcsw,
                           "majflt": ru.ru_majflt, "maxrss": ru.ru_maxrss,
                           "load": os.getloadavg()}
                    try:
                        tasks = {}
                        for tid in os.listdir("/proc/self/task"):
                            base = f"/proc/self/task/{tid}/"
                            with open(base + "comm") as c:
                                comm = c.read().strip()
                            with open(base + "stat") as s:
                                rest = s.read().rsplit(")", 1)[1].split()
                            # utime, stime: fields 14, 15 of stat(5)
                            tasks[tid] = [comm, int(rest[11]),
                                          int(rest[12])]
                            try:    # nanoseconds on the CPU, where served
                                with open(base + "schedstat") as s:
                                    tasks[tid].append(
                                        int(s.read().split()[0]))
                            except (OSError, ValueError, IndexError):
                                pass
                        row["tasks"] = tasks
                    except Exception as e:  # noqa: BLE001 - a probe
                        row["tasks_error"] = repr(e)
                    f.write(json.dumps(row) + "\n")
                    f.flush()
                    stop.wait(1.0)

        names = []
        try:
            import faulthandler

            import jax

            if jax.default_backend() != "tpu":
                raise RuntimeError("no TPU backend: get_metric not called")
            from libtpu.sdk import tpumonitoring as mon

            # get_metric holds the GIL while it runs (seen in the sandbox,
            # where it never returns): a call that hangs here takes the
            # whole process with it, so each metric's FIRST call is made
            # alone, its name written down before it, under a watchdog
            # that needs no GIL and ends the process (the run then fails
            # cleanly, and first.jsonl's last line names the metric)
            with open(os.path.join(out_dir, "first.jsonl"), "w") as f:
                for name in mon.list_supported_metrics():
                    f.write(json.dumps({"calling": name}) + "\n")
                    f.flush()
                    os.fsync(f.fileno())
                    faulthandler.dump_traceback_later(20, exit=True)
                    t0 = time.perf_counter()
                    try:
                        m = mon.get_metric(name)
                        row = {"name": name, "data": list(m.data()),
                               "description": m.description()}
                        names.append(name)
                    except Exception as e:  # noqa: BLE001 - a probe
                        row = {"name": name, "error": repr(e)}
                    faulthandler.cancel_dump_traceback_later()
                    row["took"] = time.perf_counter() - t0
                    f.write(json.dumps(row, default=repr) + "\n")
                    f.flush()
        except Exception as e:  # noqa: BLE001 - a probe
            with open(os.path.join(out_dir, "import_error.txt"), "w") as f:
                f.write(repr(e))
        threads = [threading.Thread(target=metric, args=(n,), daemon=True,
                                    name="probe-" + n) for n in names]
        threads.append(threading.Thread(target=host, daemon=True,
                                        name="probe-host"))
        for t in threads:
            t.start()
        try:
            return train_loop(config)
        finally:
            stop.set()
            for t in threads:
                t.join(1.0)

    return loop


def summarise(out_dir):
    summary = {}
    for fname in sorted(os.listdir(out_dir)):
        if not fname.endswith(".jsonl") or fname in ("host.jsonl",
                                                      "first.jsonl"):
            continue
        with open(os.path.join(out_dir, fname)) as f:
            rows = [json.loads(line) for line in f if line.strip()]
        datas = [json.dumps(r.get("data")) for r in rows if "data" in r]
        changes = sum(a != b for a, b in zip(datas, datas[1:]))
        summary[fname[:-6]] = {
            "calls": len(rows),
            "errors": sorted({r["error"] for r in rows if "error" in r}),
            "slowest_s": max((r["took"] for r in rows), default=None),
            "median_s": sorted(r["took"] for r in rows)[len(rows) // 2]
            if rows else None,
            "distinct": len(set(datas)), "changes": changes,
            "description": next((r["description"] for r in rows
                                 if "description" in r), None),
            "first": rows[0].get("data") if rows else None,
            "last": rows[-1].get("data") if rows else None}
    for fname in ("first.jsonl", "import_error.txt"):
        if os.path.exists(os.path.join(out_dir, fname)):
            with open(os.path.join(out_dir, fname)) as f:
                print(fname, f.read(), file=sys.stderr)
    with open(os.path.join(out_dir, "summary.json"), "w") as f:
        json.dump(summary, f, indent=1)
    print(json.dumps(summary, indent=1), file=sys.stderr)


def main() -> int:
    from pr54_worker_exec import run_wrapped    # beside this file

    out_dir = os.path.abspath(sys.argv[1])
    rc = run_wrapped(lambda loop: probing(loop, out_dir), sys.argv[2:])
    summarise(out_dir)
    return rc


if __name__ == "__main__":
    sys.exit(main())
