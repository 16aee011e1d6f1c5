"""Whole-program graftcheck engine tests.

Covers the cross-module fixture packages under
tests/_graftcheck_fixtures/ (a 3-file deadlock cycle, a
single-concurrency self-call, helper-laundered unserializable args, a
mesh/axis mismatch split across meshdef/kernel files, GC008 call-graph
binding), cache behavior (hit/miss/invalidation on edit), SARIF output
validation, baseline files, the DOT graph dump, and the one-run
tree-clean regression for every engine-backed rule family.
"""
import json
import os
import shutil

import pytest

from ray_tpu.devtools import graftcheck
from ray_tpu.devtools.graftcheck import check_source
from ray_tpu.devtools.graftcheck.engine import (check_project,
                                                reverse_dependency_closure,
                                                to_dot)

FIXTURES = os.path.join(os.path.dirname(__file__), "_graftcheck_fixtures")
REPO = os.path.join(os.path.dirname(__file__), "..")


def run_pkg(pkg, rules=None):
    res = check_project([os.path.join(FIXTURES, pkg)], rules=rules,
                        cache_path=None, root=FIXTURES)
    return res


def rules_of(res):
    return sorted({f.rule for f in res.findings})


# ---------------------------------------------------------------------------
# GC010 — deadlock cycles


class TestGC010:
    def test_three_file_cycle_detected_with_full_path(self):
        res = run_pkg("deadlock_pkg", rules={"GC010"})
        assert rules_of(res) == ["GC010"]
        assert len(res.findings) == 1
        msg = res.findings[0].message
        # every hop appears with its file:line
        assert "deadlock_pkg.a.A.ping" in msg
        assert "deadlock_pkg.b.B.pong" in msg
        assert "deadlock_pkg.c.C.relay" in msg
        for f, line in (("a.py", 19), ("b.py", 14), ("c.py", 15)):
            assert f"{f}:{line}" in msg, (f, line, msg)

    def test_direct_transport_cycle_detected(self):
        """Direct dispatch (ISSUE 6) changes the transport, not the call
        graph: a wait cycle whose hops will run worker-to-worker — one
        spelled with the method-level .options(...).remote() form the
        direct path encourages — must still trip GC010."""
        res = run_pkg("direct_pkg", rules={"GC010"})
        assert rules_of(res) == ["GC010"]
        assert len(res.findings) == 1
        msg = res.findings[0].message
        assert "direct_pkg.ping.Ping.serve" in msg
        assert "direct_pkg.pong.Pong.serve" in msg

    def test_method_options_submit_edge_extracted(self):
        """h.m.options(num_returns=...).remote() produces the same h.m
        submit edge as the bare spelling (v1 dropped it entirely)."""
        import ast as _ast

        from ray_tpu.devtools.graftcheck.summary import extract

        src = (
            "import ray_tpu\n"
            "def go(h):\n"
            "    return h.work.options(num_returns=2).remote(1)\n"
        )
        s, _ = extract("m.py", src, _ast.parse(src), "m")
        subs = s["functions"]["go"]["submits"]
        assert len(subs) == 1
        assert subs[0]["form"] == "method"
        assert subs[0]["method"] == "work"

    def test_single_concurrency_self_call_flagged(self):
        res = run_pkg("selfcall_pkg", rules={"GC010"})
        assert rules_of(res) == ["GC010"]
        assert len(res.findings) == 1
        f = res.findings[0]
        assert f.path.endswith("worker.py")
        assert "Worker.step" in f.message

    def test_max_concurrency_escape_stays_clean(self):
        res = run_pkg("selfcall_pkg", rules={"GC010"})
        # concurrent_ok.py has the identical shape + max_concurrency=4
        assert not any(f.path.endswith("concurrent_ok.py")
                       for f in res.findings)

    def test_single_module_cycle_via_check_source(self):
        src = """
import ray_tpu

@ray_tpu.remote
class A:
    def __init__(self, peer: "B"):
        self.peer = peer
    def ping(self, x):
        return ray_tpu.get(self.peer.pong.remote(x))

@ray_tpu.remote
class B:
    def __init__(self, peer: "A"):
        self.peer = peer
    def pong(self, x):
        return ray_tpu.get(self.peer.ping.remote(x))
"""
        found = {f.rule for f in check_source(src, "cyc.py",
                                              rules={"GC010"})}
        assert found == {"GC010"}

    def test_cycle_through_helper_waited_submit(self):
        # the wait can hide one level down: fetch(h.m.remote(x)) where
        # fetch() blocks in get() is still a synchronous edge
        src = """
import ray_tpu

def fetch(ref):
    return ray_tpu.get(ref)

@ray_tpu.remote
class A:
    def __init__(self, peer: "B"):
        self.peer = peer
    def ping(self, x):
        return fetch(self.peer.pong.remote(x))

@ray_tpu.remote
class B:
    def __init__(self, peer: "A"):
        self.peer = peer
    def pong(self, x):
        return fetch(self.peer.ping.remote(x))
"""
        found = {f.rule for f in check_source(src, "h.py",
                                              rules={"GC010"})}
        assert found == {"GC010"}

    def test_async_submit_without_get_is_not_a_cycle(self):
        src = """
import ray_tpu

@ray_tpu.remote
class A:
    def __init__(self, peer: "B"):
        self.peer = peer
    def ping(self, x):
        return self.peer.pong.remote(x)   # ref passed, never waited

@ray_tpu.remote
class B:
    def __init__(self, peer: "A"):
        self.peer = peer
    def pong(self, x):
        return self.peer.ping.remote(x)
"""
        assert check_source(src, "ok.py", rules={"GC010"}) == []

    def test_suppression_on_any_edge_silences_cycle(self):
        src = """
import ray_tpu

@ray_tpu.remote
class A:
    def __init__(self, peer: "B"):
        self.peer = peer
    def ping(self, x):
        # graftcheck: disable=GC010 bounded two-hop handshake by design
        return ray_tpu.get(self.peer.pong.remote(x))

@ray_tpu.remote
class B:
    def __init__(self, peer: "A"):
        self.peer = peer
    def pong(self, x):
        return ray_tpu.get(self.peer.ping.remote(x))
"""
        assert check_source(src, "sup.py", rules={"GC010"}) == []


# ---------------------------------------------------------------------------
# GC011 — serialization flow


class TestGC011:
    def test_helper_laundered_arg_cross_module(self):
        res = run_pkg("serial_pkg", rules={"GC011"})
        assert rules_of(res) == ["GC011"]
        by_line = {f.line: f for f in res.findings}
        # direct helper arg, indirect (two-hop) helper arg, task return
        assert 22 in by_line and "make_lock()" in by_line[22].message
        assert 23 in by_line \
            and "make_lock_indirect()" in by_line[23].message
        assert any("leak_return" in f.message for f in res.findings)
        # the plain-data path stays clean
        assert 21 not in by_line

    def test_local_ctor_arg_and_suppression(self):
        src = """
import threading
import ray_tpu

@ray_tpu.remote
def task(x):
    return x

def bad():
    return task.remote(threading.Lock())

def reviewed():
    return task.remote(threading.Lock())  # graftcheck: disable=GC011 negative-path test input
"""
        fs = check_source(src, "f.py", rules={"GC011"})
        assert [f.line for f in fs] == [10]

    def test_plain_values_stay_clean(self):
        src = """
import ray_tpu

def make_payload():
    return {"a": 1}

@ray_tpu.remote
def task(x):
    return x

def driver():
    return task.remote(make_payload())
"""
        assert check_source(src, "ok.py", rules={"GC011"}) == []


# ---------------------------------------------------------------------------
# interprocedural GC001 / GC003


class TestInterprocedural:
    def test_helper_get_one_level(self):
        src = """
import ray_tpu

def fetch(ref):
    return ray_tpu.get(ref)

@ray_tpu.remote
def outer(ref):
    return fetch(ref)
"""
        fs = check_source(src, "ip.py", rules={"GC001"})
        assert len(fs) == 1 and fs[0].line == 9
        assert "fetch()" in fs[0].message

    def test_suppressed_helper_get_stays_quiet(self):
        src = """
import ray_tpu

def fetch(ref):
    return ray_tpu.get(ref)  # graftcheck: disable=GC001 bounded depth

@ray_tpu.remote
def outer(ref):
    return fetch(ref)
"""
        assert check_source(src, "ip.py", rules={"GC001"}) == []

    def test_helper_global_write(self):
        src = """
import ray_tpu

COUNT = 0

def bump():
    global COUNT
    COUNT += 1

@ray_tpu.remote
def task():
    bump()
"""
        fs = check_source(src, "g.py", rules={"GC003"})
        assert len(fs) == 1 and fs[0].line == 12
        assert "COUNT" in fs[0].message

    def test_helper_called_from_driver_is_fine(self):
        src = """
import ray_tpu

def fetch(ref):
    return ray_tpu.get(ref)

def driver(ref):
    return fetch(ref)
"""
        assert check_source(src, "d.py", rules={"GC001", "GC003"}) == []


# ---------------------------------------------------------------------------
# GC020 / GC021 — SPMD rules


class TestSPMD:
    def test_cross_file_mesh_axis_mismatch(self):
        res = run_pkg("spmd_pkg", rules={"GC020", "GC021"})
        assert rules_of(res) == ["GC020", "GC021"]
        gc020 = [f for f in res.findings if f.rule == "GC020"]
        assert len(gc020) == 1
        assert "'pp'" in gc020[0].message
        assert "dp" in gc020[0].message and "tp" in gc020[0].message
        gc021 = [f for f in res.findings if f.rule == "GC021"]
        assert len(gc021) == 1
        assert "1 entry" in gc021[0].message
        # good_kernel (same file) stays clean
        assert all(f.line < 24 for f in res.findings), res.findings

    def test_sharding_layer_idioms(self):
        """ISSUE 11 fixture package: kernels written against the
        sharding layer's owning-mesh idiom (layoutdef.OWNER_MESH +
        axis_names= vocabulary, FsdpPlane-shaped nested bodies). GC020
        flags the collective over the unbound 'dp' axis, GC021 the
        in_specs/arity mismatch through the update-body signature;
        good_plane stays clean."""
        res = run_pkg("sharding_pkg", rules={"GC020", "GC021"})
        assert rules_of(res) == ["GC020", "GC021"]
        gc020 = [f for f in res.findings if f.rule == "GC020"]
        assert len(gc020) == 1
        assert "'dp'" in gc020[0].message
        assert "fsdp" in gc020[0].message
        assert gc020[0].path.endswith("plane.py")
        gc021 = [f for f in res.findings if f.rule == "GC021"]
        assert len(gc021) == 1
        assert "2 entries" in gc021[0].message
        # both findings land in the bad kernels, none in good_plane
        assert all(f.line < 42 for f in res.findings), res.findings

    def test_shipped_sharding_tree_is_clean(self):
        """The shipped sharding subsystem — including the quantized
        codec kernels (parallel/sharding/codec.py, ISSUE 13) — sweeps
        clean under the SPMD family it introduces idioms for (the
        tree-wide sweep below covers it too; this pins the subsystem on
        its own so a local regression names the right culprit)."""
        res = check_project(
            [os.path.join(REPO, "ray_tpu", "parallel", "sharding")],
            rules={"GC020", "GC021", "GC022"}, cache_path=None,
            root=os.path.join(REPO, "ray_tpu"))
        assert res.errors == 0
        assert [f.render() for f in res.findings] == []

    def test_codec_kernel_idioms(self):
        """ISSUE 13 fixture package: quantize→collective→dequantize
        shard_map kernels in the codec-plane idiom. GC020 flags the
        payload all_to_all over the unbound 'tp' axis (resolved
        cross-file through meshdef.CODEC_MESH), GC021 the one-spec
        in_specs against the two-argument (payload, scales) dequantize
        body; the well-formed quantized scatter stays clean."""
        res = run_pkg("codec_pkg", rules={"GC020", "GC021"})
        assert rules_of(res) == ["GC020", "GC021"]
        gc020 = [f for f in res.findings if f.rule == "GC020"]
        assert len(gc020) == 1
        assert "'tp'" in gc020[0].message
        assert "dp" in gc020[0].message
        assert gc020[0].path.endswith("kernels.py")
        gc021 = [f for f in res.findings if f.rule == "GC021"]
        assert len(gc021) == 1
        assert "1 entry" in gc021[0].message
        # both findings land in the bad kernels, none in
        # good_quantized_scatter below them
        assert all(f.line < 47 for f in res.findings), res.findings

    def test_symbolic_axis_names_match(self):
        # pipeline.py-style: axis_names=frozenset({pp_axis}) with the
        # collectives using the same symbol — must stay clean
        src = """
import jax
from ray_tpu.jax_compat import shard_map

def pipeline(mesh, x, pp_axis="pp"):
    def body(v):
        return jax.lax.psum(v, pp_axis)
    fn = shard_map(body, mesh=mesh, in_specs=(jax.P(),),
                   out_specs=jax.P(), axis_names=frozenset({pp_axis}))
    return fn(x)
"""
        assert check_source(src, "p.py", rules={"GC020", "GC021"}) == []

    def test_unknown_mesh_stays_silent(self):
        src = """
import jax

def kern(mesh, x):
    def body(v):
        return jax.lax.psum(v, "anything")
    return jax.shard_map(body, mesh=mesh, in_specs=(jax.P(),),
                         out_specs=jax.P())(x)
"""
        assert check_source(src, "u.py", rules={"GC020"}) == []

    def test_pallas_blockspecs_never_match(self):
        # pallas_call also takes in_specs=[...]; only real shard_map
        # callees are checked
        src = """
import jax
from jax.experimental import pallas as pl

def kern(x):
    return pl.pallas_call(lambda r, o: None,
                          in_specs=[pl.BlockSpec((8,), lambda i: i)],
                          out_specs=pl.BlockSpec((8,), lambda i: i))(x)
"""
        assert check_source(src, "pl.py", rules={"GC020", "GC021"}) == []

    def test_lambda_arity_mismatch(self):
        src = """
import jax

def kern(mesh, q, k):
    fn = jax.shard_map(lambda q, k, v: q, mesh=mesh,
                       in_specs=(jax.P(), jax.P()), out_specs=jax.P())
    return fn(q, k)
"""
        fs = check_source(src, "l.py", rules={"GC021"})
        assert len(fs) == 1 and "2 entries" in fs[0].message

    def test_partial_bound_kwargs_counted(self):
        src = """
import functools
import jax
from ray_tpu.jax_compat import shard_map

def attention(q, k, v, axis_name="sp", causal=True):
    return q

def wrapper(mesh, q, k, v):
    fn = shard_map(
        functools.partial(attention, axis_name="sp", causal=False),
        mesh=mesh, in_specs=(jax.P(), jax.P(), jax.P()),
        out_specs=jax.P())
    return fn(q, k, v)
"""
        assert check_source(src, "pt.py", rules={"GC021"}) == []


# ---------------------------------------------------------------------------
# GC022 — donated buffers


class TestGC022:
    def test_read_after_donation(self):
        src = """
import functools
import jax

def step(params, batch):
    @functools.partial(jax.jit, donate_argnums=(0,))
    def update(p, b):
        return p
    new_params = update(params, batch)
    return params
"""
        fs = check_source(src, "d.py", rules={"GC022"})
        assert len(fs) == 1 and fs[0].line == 10
        assert "'params'" in fs[0].message

    def test_rebinding_is_clean(self):
        src = """
import jax

def step(params, opt, batch):
    update = jax.jit(lambda p, o, b: (p, o), donate_argnums=(0, 1))
    params, opt = update(params, opt, batch)
    return params, opt
"""
        assert check_source(src, "ok.py", rules={"GC022"}) == []

    def test_non_donated_position_is_clean(self):
        src = """
import jax

def step(params, batch):
    update = jax.jit(lambda p, b: p, donate_argnums=(0,))
    new = update(params, batch)
    return batch
"""
        assert check_source(src, "ok2.py", rules={"GC022"}) == []

    def test_tp_decode_donated_cache_reuse(self):
        """The sharded-serve idiom (ISSUE 11): the tp decode step
        donates its KV cache buffers. Reading the donated cache var
        after the call is the bug; the engine's rebind-the-cache idiom
        (cache = decode(...)) is the fix and stays clean."""
        src = """
import functools
import jax

def serve_decode(params, kc, vc, tokens):
    decode = jax.jit(lambda p, k, v, t: (t, k, v),
                     donate_argnums=(1, 2))
    logits, new_k, new_v = decode(params, kc, vc, tokens)
    return logits, kc
"""
        fs = check_source(src, "tp.py", rules={"GC022"})
        assert len(fs) == 1
        assert "'kc'" in fs[0].message
        ok = """
import functools
import jax

def serve_decode(params, kc, vc, tokens):
    decode = jax.jit(lambda p, k, v, t: (t, k, v),
                     donate_argnums=(1, 2))
    logits, kc, vc = decode(params, kc, vc, tokens)
    return logits, kc
"""
        assert check_source(ok, "tp_ok.py", rules={"GC022"}) == []


# ---------------------------------------------------------------------------
# GC008 — call-graph-resolved binding


class TestGC008Resolution:
    def test_same_named_method_on_unrelated_class_is_clean(self):
        res = run_pkg("gc008_pkg", rules={"GC008"})
        files_lines = {(os.path.basename(f.path), f.line)
                       for f in res.findings}
        # Dirty.fwd (resolved receiver) and Opaque.run (fallback) flagged
        assert ("bound_bad.py", 12) in files_lines
        assert ("bound_bad.py", 18) in files_lines
        # Unrelated.step shares Pipeline.step's NAME but resolves to a
        # different class: no fallback needed, stays clean
        assert not any(os.path.basename(f.path) == "actors.py"
                       for f in res.findings), res.findings

    def test_list_of_handles_loop_receiver_resolves(self):
        # build_from_list binds Pipeline.step via a loop variable over a
        # list of handles; Unrelated.step must still stay clean (above),
        # proving the receiver resolved rather than name-matched
        res = run_pkg("gc008_pkg", rules={"GC008"})
        assert all(os.path.basename(f.path) == "bound_bad.py"
                   for f in res.findings)


class TestIterativeBindPattern:
    """ISSUE 8: stage methods bound into a CYCLIC compiled graph (the
    pipeline-engine shape — fwd chain out, bwd chain back, the same
    actors twice on the chain) with the engine's own dynamic surface
    doing driver-side gets between steps."""

    def test_pure_bound_stage_methods_stay_gc008_clean(self):
        res = run_pkg("iterbind_pkg", rules={"GC008"})
        # only the DirtyStage positive control fires; PipeStage's
        # fwd/bwd/update are bound on a cycle but pure — clean, and the
        # engine's internal get()s are not attributed to them
        assert len(res.findings) == 1, res.findings
        f = res.findings[0]
        assert os.path.basename(f.path) == "stages.py"
        assert f.line == 39  # DirtyStage.forward's dynamic submit

    def test_cyclic_bind_dataflow_is_not_a_gc010_deadlock(self):
        # the a->b->a bind shape is channel dataflow, not synchronous
        # waiting; no stage method blocks on a peer call
        res = run_pkg("iterbind_pkg", rules={"GC010"})
        assert res.findings == [], res.findings

    def test_real_engine_module_clean_for_bind_rules(self):
        # the regression the fixture models: the shipped engine
        # (train/pipeline_cgraph.py + cgraph/executor.py) must not trip
        # the bind/deadlock rules on its own internal gets and loops
        res = check_project(
            [os.path.join(REPO, "ray_tpu", "train"),
             os.path.join(REPO, "ray_tpu", "cgraph")],
            rules={"GC008", "GC010"}, cache_path=None,
            root=os.path.join(REPO, "ray_tpu"))
        assert res.findings == [], res.findings


# ---------------------------------------------------------------------------
# cache


class TestCache:
    def _write_proj(self, tmp_path):
        (tmp_path / "mod.py").write_text(
            "import ray_tpu\n"
            "@ray_tpu.remote\n"
            "def f(r):\n"
            "    return ray_tpu.get(r)\n")
        (tmp_path / "clean.py").write_text("x = 1\n")

    def test_hit_miss_and_invalidation_on_edit(self, tmp_path):
        self._write_proj(tmp_path)
        cache = str(tmp_path / "cache.json")
        res1 = check_project([str(tmp_path)], cache_path=cache)
        assert res1.parsed == 2 and res1.cached == 0
        assert [f.rule for f in res1.findings] == ["GC001"]

        res2 = check_project([str(tmp_path)], cache_path=cache)
        assert res2.parsed == 0 and res2.cached == 2
        assert [f.rule for f in res2.findings] == ["GC001"]

        # fixing the file invalidates exactly its entry
        (tmp_path / "mod.py").write_text(
            "import ray_tpu\n"
            "@ray_tpu.remote\n"
            "def f(r):\n"
            "    return r\n")
        res3 = check_project([str(tmp_path)], cache_path=cache)
        assert res3.parsed == 1 and res3.cached == 1
        assert res3.findings == []

    def test_cached_findings_identical_to_cold(self, tmp_path):
        self._write_proj(tmp_path)
        cache = str(tmp_path / "cache.json")
        cold = check_project([str(tmp_path)], cache_path=cache)
        warm = check_project([str(tmp_path)], cache_path=cache)
        assert [f.as_dict() for f in cold.findings] \
            == [f.as_dict() for f in warm.findings]

    def test_package_dir_invocation_keeps_absolute_imports(self, tmp_path):
        # `graftcheck pkg/` must anchor module names at the PACKAGE
        # root, or `from pkg.b import B` resolves to nothing and every
        # cross-file rule silently dies
        pkg = tmp_path / "pkg"
        pkg.mkdir()
        (pkg / "__init__.py").write_text("")
        (pkg / "a.py").write_text(
            "import ray_tpu\n"
            "from pkg.b import B\n"
            "@ray_tpu.remote\n"
            "class A:\n"
            "    def __init__(self, peer: B):\n"
            "        self.peer = peer\n"
            "    def ping(self, x):\n"
            "        return ray_tpu.get(self.peer.pong.remote(x))\n")
        (pkg / "b.py").write_text(
            "import ray_tpu\n"
            "@ray_tpu.remote\n"
            "class B:\n"
            "    def __init__(self, peer: 'pkg.a.A'):\n"
            "        self.peer = peer\n"
            "    def pong(self, x):\n"
            "        return ray_tpu.get(self.peer.ping.remote(x))\n")
        res = check_project([str(pkg)], rules={"GC010"}, cache_path=None)
        assert [f.rule for f in res.findings] == ["GC010"]

    def test_corrupt_cache_is_ignored(self, tmp_path):
        self._write_proj(tmp_path)
        cache = tmp_path / "cache.json"
        cache.write_text("{not json")
        res = check_project([str(tmp_path)], cache_path=str(cache))
        assert res.parsed == 2
        assert [f.rule for f in res.findings] == ["GC001"]


# ---------------------------------------------------------------------------
# SARIF


class TestSarif:
    def test_sarif_document_structure(self, tmp_path):
        self_dir = os.path.join(FIXTURES, "serial_pkg")
        out = tmp_path / "out.sarif"
        rc = graftcheck.main(["--no-cache", "--sarif", str(out),
                              "--rules", "GC011", self_dir])
        assert rc == 1   # findings exist
        doc = json.loads(out.read_text())
        # SARIF 2.1.0 structural requirements (what GitHub ingests)
        assert doc["version"] == "2.1.0"
        assert doc["$schema"].endswith("sarif-schema-2.1.0.json")
        (run,) = doc["runs"]
        driver = run["tool"]["driver"]
        assert driver["name"] == "graftcheck"
        rule_ids = [r["id"] for r in driver["rules"]]
        assert rule_ids == sorted(rule_ids)
        assert "GC011" in rule_ids
        for r in driver["rules"]:
            assert r["shortDescription"]["text"]
        assert run["results"], "expected GC011 results"
        for result in run["results"]:
            assert result["ruleId"] in rule_ids
            assert result["level"] == "warning"
            assert result["message"]["text"].startswith(result["ruleId"])
            (loc,) = result["locations"]
            phys = loc["physicalLocation"]
            uri = phys["artifactLocation"]["uri"]
            assert not uri.startswith("/") and "\\" not in uri
            region = phys["region"]
            assert region["startLine"] >= 1
            assert region["startColumn"] >= 1
            assert result["partialFingerprints"]["graftcheck/v1"]

    def test_jsonschema_validation_when_available(self, tmp_path):
        jsonschema = pytest.importorskip("jsonschema")
        from ray_tpu.devtools.graftcheck.sarif import to_sarif
        from ray_tpu.devtools.graftcheck.local import Finding

        doc = to_sarif([Finding("a.py", 3, 1, "GC001", "m")])
        # minimal inline schema for the parts code-scanning requires
        schema = {
            "type": "object",
            "required": ["version", "runs"],
            "properties": {
                "version": {"const": "2.1.0"},
                "runs": {"type": "array", "minItems": 1, "items": {
                    "type": "object",
                    "required": ["tool", "results"],
                    "properties": {"tool": {
                        "type": "object", "required": ["driver"]}},
                }},
            },
        }
        jsonschema.validate(doc, schema)


# ---------------------------------------------------------------------------
# baseline


class TestBaseline:
    def test_write_then_filter(self, tmp_path):
        proj = tmp_path / "proj"
        proj.mkdir()
        bad = proj / "bad.py"
        bad.write_text(
            "import ray_tpu\n"
            "@ray_tpu.remote\n"
            "def f(r):\n"
            "    return ray_tpu.get(r)\n")
        base = str(tmp_path / "base.json")
        rc = graftcheck.main(["--no-cache", "--write-baseline", base,
                              str(proj)])
        assert rc == 0
        # baselined: clean exit
        assert graftcheck.main(["--no-cache", "--baseline", base,
                                str(proj)]) == 0
        # a new finding in another file still fails
        (proj / "new.py").write_text(
            "import ray_tpu\n"
            "@ray_tpu.remote\n"
            "def g(r):\n"
            "    return ray_tpu.get(r)\n")
        assert graftcheck.main(["--no-cache", "--baseline", base,
                                str(proj)]) == 1

    def test_editing_flagged_line_resurrects(self, tmp_path):
        proj = tmp_path / "proj"
        proj.mkdir()
        bad = proj / "bad.py"
        bad.write_text(
            "import ray_tpu\n"
            "@ray_tpu.remote\n"
            "def f(r):\n"
            "    return ray_tpu.get(r)\n")
        base = str(tmp_path / "base.json")
        assert graftcheck.main(["--no-cache", "--write-baseline", base,
                                str(proj)]) == 0
        # unrelated edits above the finding do NOT resurrect it
        bad.write_text(
            "import ray_tpu\n"
            "# a comment\n"
            "@ray_tpu.remote\n"
            "def f(r):\n"
            "    return ray_tpu.get(r)\n")
        assert graftcheck.main(["--no-cache", "--baseline", base,
                                str(proj)]) == 0
        # editing the flagged line itself does
        bad.write_text(
            "import ray_tpu\n"
            "@ray_tpu.remote\n"
            "def f(r):\n"
            "    return ray_tpu.get(r) + 1\n")
        assert graftcheck.main(["--no-cache", "--baseline", base,
                                str(proj)]) == 1


# ---------------------------------------------------------------------------
# graph subcommand / DOT


class TestGraph:
    def test_dot_contains_cycle_edges(self):
        res = run_pkg("deadlock_pkg")
        dot = to_dot(res.graph)
        assert dot.startswith("digraph remote_calls")
        assert '"deadlock_pkg.a.A.ping"' in dot
        assert "sync get" in dot
        # the three cycle edges are present
        assert dot.count("sync get") >= 3

    def test_graph_cli(self, tmp_path, capsys):
        out = tmp_path / "g.dot"
        rc = graftcheck.main(["graph", "--no-cache", "--out", str(out),
                              os.path.join(FIXTURES, "deadlock_pkg")])
        assert rc == 0
        text = out.read_text()
        assert "digraph remote_calls" in text
        assert "A.ping" in text

    def test_bind_edges_in_graph(self):
        res = run_pkg("gc008_pkg")
        dot = to_dot(res.graph)
        assert 'label="bind"' in dot


# ---------------------------------------------------------------------------
# tree-clean regressions: one per engine-backed rule family (mirrors the
# GC007 pattern), sharing a single engine run to keep tier-1 time flat


@pytest.fixture(scope="module")
def tree_result():
    res = check_project(
        [os.path.join(REPO, "ray_tpu"), os.path.join(REPO, "examples"),
         os.path.join(REPO, "tests")],
        rules={"GC008", "GC010", "GC011", "GC020", "GC021", "GC022",
               "GC030", "GC031", "GC032", "GC033",
               "GC040", "GC041", "GC042", "GC043", "GC044",
               "GC050", "GC051", "GC052", "GC053", "GC054"},
        cache_path=None)
    assert res.errors == 0
    return res


def _tree_findings(res, rules):
    return [f.render() for f in res.findings if f.rule in rules]


def test_library_tree_is_gc010_gc011_clean(tree_result):
    """The sweep satellite stays swept: no un-annotated deadlock cycles
    or serialization-flow findings (incl. the interprocedural layer)
    anywhere in ray_tpu/, examples/ or tests/."""
    assert _tree_findings(tree_result, {"GC010", "GC011"}) == []


def test_library_tree_is_spmd_clean(tree_result):
    """No un-annotated GC020/GC021/GC022 SPMD findings on the tree
    (parallel/, ops/, rllib donation patterns, test kernels)."""
    assert _tree_findings(tree_result, {"GC020", "GC021", "GC022"}) == []


def test_library_tree_is_gc008_clean_under_call_graph(tree_result):
    """Call-graph-resolved GC008 finds no un-annotated dynamic work in
    compiled-graph-bound methods tree-wide."""
    assert _tree_findings(tree_result, {"GC008"}) == []


# ---------------------------------------------------------------------------
# prefix-cache fixture package (ISSUE 14)


class TestPrefixPkg:
    LOCAL = {"GC001", "GC002", "GC003", "GC004", "GC005", "GC006",
             "GC007", "GC008", "GC009", "GC012"}

    def test_refcount_leak_shaped_positives(self):
        """The two leak-shaped bugs in leaky.py fire — an alloc path
        that early-returns holding the scheduler lock (GC006) and a
        release swallowed by a bare except (GC005) — while the clean
        radix manager next to them stays silent under the full
        GC001–GC012 local family."""
        res = run_pkg("prefix_pkg", rules=self.LOCAL)
        assert rules_of(res) == ["GC005", "GC006"], res.findings
        assert all(f.path.endswith("leaky.py") for f in res.findings), \
            res.findings
        gc006 = [f for f in res.findings if f.rule == "GC006"]
        assert len(gc006) == 1 and "leak" in gc006[0].message
        gc005 = [f for f in res.findings if f.rule == "GC005"]
        assert len(gc005) == 1

    def test_clean_manager_is_clean(self):
        """radix.py alone — the shipped-idiom shape (with-locks, paired
        retain/release, guard-with-reraise) — produces zero findings."""
        res = check_project(
            [os.path.join(FIXTURES, "prefix_pkg", "radix.py")],
            rules=self.LOCAL, cache_path=None, root=FIXTURES)
        assert [f.render() for f in res.findings] == []

    def test_shipped_llm_serve_tree_is_clean(self):
        """The shipped prefix-cache subsystem (serve/llm/ + the radix
        tree + the session-aware routing files) sweeps clean under
        every local rule AND the whole-program families — a local
        regression names the right culprit without waiting for the
        tree-wide sweep."""
        res = check_project(
            [os.path.join(REPO, "ray_tpu", "serve")],
            rules=self.LOCAL | {"GC010", "GC011"},
            cache_path=None, root=os.path.join(REPO, "ray_tpu"))
        assert res.errors == 0
        assert [f.render() for f in res.findings] == []


# ---------------------------------------------------------------------------
# lifecycle rules GC030-033 (graftcheck v3: CFG + dataflow)


LIFECYCLE = {"GC030", "GC031", "GC032", "GC033"}


class TestLifecycleFixtures:
    """The lifecycle_pkg fixture pack: every seeded positive fires on
    its line, every clean shape stays silent, and the cross-file
    ownership pendings resolve both ways."""

    @pytest.fixture(scope="class")
    def res(self):
        return run_pkg("lifecycle_pkg", rules=LIFECYCLE)

    def _at(self, res, fname, rule):
        return [f for f in res.findings
                if f.path.endswith(fname) and f.rule == rule]

    def test_clean_shapes_are_silent(self, res):
        """try/finally, with, ownership via return / self-store /
        constructor, alloc-None guards, refcounted retain+2xfree,
        best-effort close, try-acquire probes, accumulator loops."""
        assert self._at(res, "clean.py", "GC030") == []
        assert not any(f.path.endswith("clean.py") for f in res.findings)

    def test_swallowed_release_is_gc032(self, res):
        """The PR-13 known-shape regression, path-proven: an exception
        before the free lands in a swallowing handler and rejoins the
        normal flow holding the blocks."""
        hits = self._at(res, "leaky.py", "GC032")
        assert len(hits) == 1 and hits[0].line == 17
        assert "swallows" in hits[0].message

    def test_loop_reacquire_is_gc030(self, res):
        hits = [f for f in self._at(res, "leaky.py", "GC030")
                if f.line == 27]
        assert hits and any("re-acquired" in f.message for f in hits)

    def test_double_free_diamond_is_gc031(self, res):
        hits = [f for f in self._at(res, "leaky.py", "GC031")
                if f.line == 38]
        assert len(hits) == 1
        assert "double release" in hits[0].message

    def test_conditional_acquire_is_gc033(self, res):
        hits = self._at(res, "leaky.py", "GC033")
        assert [f.line for f in hits] == [47]

    def test_early_return_holding_lock_is_gc030(self, res):
        """The second known-shape regression: a return path exits with
        the lock held."""
        hits = [f for f in self._at(res, "leaky.py", "GC030")
                if f.line == 53]
        assert hits and "lock" in hits[0].message

    def test_early_return_leak_and_discarded_alloc(self, res):
        lines = {f.line for f in self._at(res, "leaky.py", "GC030")}
        assert 62 in lines     # early return past the release
        assert 71 in lines     # discarded allocation result

    def test_over_free_past_refcount_is_gc031(self, res):
        hits = [f for f in self._at(res, "leaky.py", "GC031")
                if f.line == 80]
        assert len(hits) == 1

    def test_crossfile_helper_release_is_clean(self, res):
        """A helper in another file that releases (or adopts) its
        parameter transfers ownership: no leak at the call site."""
        bad = [f for f in res.findings if f.path.endswith("crossfile.py")
               and f.line < 20]
        assert bad == [], bad

    def test_crossfile_leak_confirmed(self, res):
        """measure() provably neither releases nor keeps the blocks —
        the pending leak is CONFIRMED through the import graph."""
        hits = [f for f in self._at(res, "crossfile.py", "GC030")]
        assert [f.line for f in hits] == [22]
        assert "measure" in hits[0].message

    def test_crossfile_double_free_confirmed(self, res):
        hits = [f for f in self._at(res, "crossfile.py", "GC031")]
        assert [f.line for f in hits] == [31]
        assert "release_blocks" in hits[0].message

    def test_no_fixture_negatives(self, res):
        """Zero findings outside the seeded positive lines."""
        expect = {("leaky.py", 17), ("leaky.py", 27), ("leaky.py", 38),
                  ("leaky.py", 47), ("leaky.py", 53), ("leaky.py", 62),
                  ("leaky.py", 71), ("leaky.py", 80),
                  ("crossfile.py", 22), ("crossfile.py", 31)}
        got = {(os.path.basename(f.path), f.line) for f in res.findings}
        assert got == expect, got.symmetric_difference(expect)


class TestLifecycleCfgCorners:
    """CFG-construction corners exercised through check_source."""

    def _run(self, src):
        return [f for f in graftcheck.check_source(src, "c.py",
                                                   rules=LIFECYCLE)]

    def test_for_else_return_transfers_ownership(self):
        src = (
            "def f(pool, n, xs):\n"
            "    b = pool.alloc(n)\n"
            "    for x in xs:\n"
            "        if x:\n"
            "            break\n"
            "    else:\n"
            "        return b\n"
            "    pool.free(b)\n"
        )
        assert self._run(src) == []

    def test_for_else_leak_on_break_path(self):
        src = (
            "def f(pool, n, xs):\n"
            "    b = pool.alloc(n)\n"
            "    for x in xs:\n"
            "        if x:\n"
            "            break\n"
            "    else:\n"
            "        pool.free(b)\n"
            "        return None\n"
            "    return 1\n"
        )
        hits = self._run(src)
        assert [f.rule for f in hits] == ["GC030"]

    def test_nested_finally_releases_on_every_path(self):
        src = (
            "def f(pool, n, work):\n"
            "    b = pool.alloc(n)\n"
            "    try:\n"
            "        try:\n"
            "            work(b)\n"
            "        finally:\n"
            "            pool.free(b)\n"
            "    finally:\n"
            "        work(None)\n"
        )
        assert self._run(src) == []

    def test_raise_in_except_is_not_a_swallow(self):
        src = (
            "def f(pool, n, work):\n"
            "    b = pool.alloc(n)\n"
            "    try:\n"
            "        work(b)\n"
            "        pool.free(b)\n"
            "    except Exception:\n"
            "        raise RuntimeError('boom')\n"
        )
        assert self._run(src) == []

    def test_release_in_handler_is_clean(self):
        src = (
            "def f(pool, n, work):\n"
            "    b = pool.alloc(n)\n"
            "    try:\n"
            "        work(b)\n"
            "        pool.free(b)\n"
            "    except Exception:\n"
            "        pool.free(b)\n"
        )
        assert self._run(src) == []

    def test_while_else_and_continue(self):
        src = (
            "def f(pool, n, q):\n"
            "    b = pool.alloc(n)\n"
            "    while q.pending():\n"
            "        if q.skip():\n"
            "            continue\n"
            "        q.step(n)\n"
            "    else:\n"
            "        pool.free(b)\n"
            "    return 1\n"
        )
        # while-else runs on normal loop exit (no break): released
        assert self._run(src) == []

    def test_generator_functions_skipped_with_stat(self, tmp_path):
        src = (
            "def gen(pool, n):\n"
            "    b = pool.alloc(n)\n"
            "    yield b\n"
            "def plain(pool, n):\n"
            "    b = pool.alloc(n)\n"
            "    pool.free(b)\n"
        )
        p = tmp_path / "g.py"
        p.write_text(src)
        res = check_project([str(p)], rules=LIFECYCLE, cache_path=None,
                            root=str(tmp_path))
        assert res.findings == []
        assert res.lifecycle_stats.get("fns_generators_skipped") == 1
        assert res.lifecycle_stats.get("fns_analyzed") == 1

    def test_with_manual_release_is_gc031(self):
        src = (
            "import threading\n"
            "_lk = threading.Lock()\n"
            "def f(c):\n"
            "    with _lk:\n"
            "        if c:\n"
            "            _lk.release()\n"
            "        return 1\n"
        )
        hits = self._run(src)
        assert [f.rule for f in hits] == ["GC031"]

    def test_lifecycle_stats_aggregate(self, tmp_path):
        p = tmp_path / "s.py"
        p.write_text("def f(pool):\n    b = pool.alloc(1)\n"
                     "    pool.free(b)\n")
        res = check_project([str(p)], rules=LIFECYCLE, cache_path=None,
                            root=str(tmp_path))
        st = res.lifecycle_stats
        assert st.get("cfg_nodes", 0) > 0
        assert st.get("fixpoint_iterations", 0) > 0
        assert st.get("resources") == 1

    def test_cached_lifecycle_findings_identical_to_cold(self, tmp_path):
        """Lifecycle findings + pendings ride the content-hash cache:
        a warm run reports exactly the cold run's findings without
        re-running the CFG pass."""
        pkg = os.path.join(FIXTURES, "lifecycle_pkg")
        cache = str(tmp_path / "cache.json")
        cold = check_project([pkg], rules=LIFECYCLE, cache_path=cache,
                             root=FIXTURES)
        warm = check_project([pkg], rules=LIFECYCLE, cache_path=cache,
                             root=FIXTURES)
        assert warm.parsed == 0 and warm.cached == len(warm.files)
        assert [f.render() for f in warm.findings] == \
            [f.render() for f in cold.findings]
        assert warm.findings  # the pack has positives


def test_library_tree_is_lifecycle_clean(tree_result):
    """The full-tree sweep satellite stays swept: zero un-annotated
    GC030-033 findings across ray_tpu/, examples/ and tests/ (the
    intentional long-held channel segments, actor-lifetime collective
    groups and refcount stress tests carry line annotations with
    rationale)."""
    assert _tree_findings(tree_result, LIFECYCLE) == []


# ---------------------------------------------------------------------------
# baseline fingerprints: rule id + same-text occurrence disambiguation


class TestBaselineFingerprintMasking:
    def test_same_line_different_rules_do_not_mask(self, tmp_path):
        """A GC030 and a GC032 anchored on the same line have distinct
        fingerprints: baselining one must not hide the other."""
        from ray_tpu.devtools.graftcheck import baseline
        from ray_tpu.devtools.graftcheck.local import Finding

        p = tmp_path / "x.py"
        p.write_text("pool.free(b)\n")
        f30 = Finding(str(p), 1, 1, "GC030", "leak")
        f32 = Finding(str(p), 1, 1, "GC032", "swallowed")
        bl = tmp_path / "bl.json"
        baseline.write(str(bl), [f30])
        kept = baseline.filter_findings([f30, f32], str(bl))
        assert [f.rule for f in kept] == ["GC032"]

    def test_duplicate_line_text_does_not_mask(self, tmp_path):
        """Two findings of the SAME rule on identical duplicated lines
        used to share a fingerprint — baselining one masked the other.
        The occurrence index keeps them distinct."""
        from ray_tpu.devtools.graftcheck import baseline
        from ray_tpu.devtools.graftcheck.local import Finding

        p = tmp_path / "x.py"
        p.write_text("    pool.free(b)\n" * 3)
        a = Finding(str(p), 1, 5, "GC031", "double")
        b = Finding(str(p), 3, 5, "GC031", "double")
        bl = tmp_path / "bl.json"
        baseline.write(str(bl), [a])
        kept = baseline.filter_findings([a, b], str(bl))
        assert len(kept) == 1 and kept[0].line == 3

    def test_single_occurrence_fingerprints_unchanged(self, tmp_path):
        """Index 0 is omitted from the key: existing baselines for
        non-duplicated lines keep filtering."""
        from ray_tpu.devtools.graftcheck import baseline
        from ray_tpu.devtools.graftcheck.local import Finding

        p = tmp_path / "x.py"
        p.write_text("lock.acquire()\n")
        f = Finding(str(p), 1, 1, "GC030", "leak")
        cache = {}
        assert baseline.fingerprint(f, cache) == \
            baseline.fingerprint(f, {}, 0)
        bl = tmp_path / "bl.json"
        baseline.write(str(bl), [f])
        assert baseline.filter_findings([f], str(bl)) == []


def test_sarif_includes_lifecycle_rule_metadata(tmp_path):
    """The SARIF driver carries GC030-033 rule entries so code-scanning
    renders the new family."""
    from ray_tpu.devtools.graftcheck.sarif import to_sarif
    from ray_tpu.devtools.graftcheck.local import Finding

    doc = to_sarif([Finding("a.py", 3, 1, "GC032", "swallowed release")])
    rules = {r["id"]
             for r in doc["runs"][0]["tool"]["driver"]["rules"]}
    assert {"GC030", "GC031", "GC032", "GC033"} <= rules
    assert doc["runs"][0]["results"][0]["ruleId"] == "GC032"


class TestLifecycleOwnershipEdges:
    """Review-hardening regressions: ownership transfer through
    keyword arguments, and delegation chains that leave the module."""

    def test_kwarg_constructor_takes_ownership(self):
        src = (
            "def f(pool, q, n):\n"
            "    b = pool.alloc(n)\n"
            "    q.put(_Seq(blocks=b))\n"
        )
        assert graftcheck.check_source(src, "k.py",
                                       rules=LIFECYCLE) == []

    def test_local_helper_releases_kwarg_param(self):
        src = (
            "def fin(pool, blocks):\n"
            "    pool.free(blocks)\n"
            "def f(pool, n):\n"
            "    b = pool.alloc(n)\n"
            "    fin(pool, blocks=b)\n"
        )
        assert graftcheck.check_source(src, "k2.py",
                                       rules=LIFECYCLE) == []

    def test_cross_module_delegation_chain_stays_silent(self, tmp_path):
        """A cross-module helper that hands the resource to a callee IT
        cannot resolve is not 'provably non-owning': the pending leak
        must be dismissed, not confirmed (one-hop-only summaries used
        to confirm a false GC030 here)."""
        pkg = tmp_path / "pkg"
        pkg.mkdir()
        (pkg / "__init__.py").write_text("")
        (pkg / "deep.py").write_text(
            "def real_free(pool, b):\n    pool.free(b)\n")
        (pkg / "mid.py").write_text(
            "from . import deep\n\n"
            "def delegate_free(pool, b):\n"
            "    deep.real_free(pool, b)\n")
        (pkg / "caller.py").write_text(
            "from .mid import delegate_free\n\n"
            "def go(pool, n):\n"
            "    b = pool.alloc(n)\n"
            "    delegate_free(pool, b)\n")
        res = check_project([str(pkg)], rules=LIFECYCLE,
                            cache_path=None, root=str(tmp_path))
        assert res.findings == [], [f.render() for f in res.findings]

    def test_alternating_refcount_balance_is_clean(self):
        """alloc;retain;free;retain;free;free is rc 1-2-1-2-1-0 —
        balanced; the UAF check must not fire while any acquisition
        bound to the name is still held."""
        src = (
            "def f(pool, n):\n"
            "    b = pool.alloc(n)\n"
            "    pool.retain(b)\n"
            "    pool.free(b)\n"
            "    pool.retain(b)\n"
            "    pool.free(b)\n"
            "    pool.free(b)\n"
        )
        assert graftcheck.check_source(src, "rc.py",
                                       rules=LIFECYCLE) == []

    def test_helper_routed_free_respects_refcount(self):
        """A free routed through a local helper consumes ONE
        acquisition like a direct free — rc-2 with one helper-free and
        one direct free is balanced, not a double release."""
        src = (
            "def fin(pool, b):\n"
            "    pool.free(b)\n"
            "def f(pool, n):\n"
            "    b = pool.alloc(n)\n"
            "    pool.retain(b)\n"
            "    fin(pool, b)\n"
            "    pool.free(b)\n"
        )
        assert graftcheck.check_source(src, "rc2.py",
                                       rules=LIFECYCLE) == []

    def test_helper_free_plus_direct_free_is_double(self):
        """Without the retain, the same shape IS a double release."""
        src = (
            "def fin(pool, b):\n"
            "    pool.free(b)\n"
            "def f(pool, n):\n"
            "    b = pool.alloc(n)\n"
            "    fin(pool, b)\n"
            "    pool.free(b)\n"
        )
        hits = graftcheck.check_source(src, "rc3.py", rules=LIFECYCLE)
        assert [f.rule for f in hits] == ["GC031"]

    def test_elementwise_loop_release_credits_param(self, tmp_path):
        """`for b in blocks: pool.free(b)` releases the PARAM — both
        the same-module call site and a cross-module pending must stay
        silent (the free_all cleanup-helper idiom)."""
        src = (
            "def free_all(pool, blocks):\n"
            "    for b in blocks:\n"
            "        pool.free(b)\n"
            "def caller(pool, n):\n"
            "    bs = pool.alloc(n)\n"
            "    free_all(pool, bs)\n"
        )
        assert graftcheck.check_source(src, "ew.py",
                                       rules=LIFECYCLE) == []
        pkg = tmp_path / "p"
        pkg.mkdir()
        (pkg / "__init__.py").write_text("")
        (pkg / "h.py").write_text(
            "def free_all(pool, blocks):\n"
            "    for b in blocks:\n"
            "        pool.free(b)\n")
        (pkg / "c.py").write_text(
            "from .h import free_all\n\n"
            "def go(pool, n):\n"
            "    bs = pool.alloc(n)\n"
            "    free_all(pool, bs)\n")
        res = check_project([str(pkg)], rules=LIFECYCLE,
                            cache_path=None, root=str(tmp_path))
        assert res.findings == [], [f.render() for f in res.findings]


def test_baseline_new_duplicate_above_reports_the_new_one(tmp_path):
    """A NEW identical-text finding appearing ABOVE a baselined one
    must be the one reported: suppression prefers findings on the
    lines the baseline recorded, so the new line surfaces instead of
    silently absorbing the old entry's occurrence-0 fingerprint."""
    from ray_tpu.devtools.graftcheck import baseline
    from ray_tpu.devtools.graftcheck.local import Finding

    p = tmp_path / "x.py"
    p.write_text("    pool.free(b)\n" * 5)
    old = Finding(str(p), 4, 5, "GC031", "double")
    bl = tmp_path / "bl.json"
    baseline.write(str(bl), [old])
    new = Finding(str(p), 2, 5, "GC031", "double")
    kept = baseline.filter_findings([new, old], str(bl))
    assert [f.line for f in kept] == [2]


# ---------------------------------------------------------------------------
# v4 — shape-and-spec abstract interpretation (GC040-044, CFG'd GC022)

SHAPES = frozenset({"GC022", "GC040", "GC041", "GC042", "GC043", "GC044"})


class TestShapeFixtures:
    """shapes_pkg seeds exactly one positive per v4 rule form; every
    clean counterpart lives beside it. Line pins are exact."""

    @pytest.fixture(scope="class")
    def res(self):
        return run_pkg("shapes_pkg", rules=SHAPES)

    def _at(self, res, fname, rule):
        return sorted(f.line for f in res.findings
                      if f.rule == rule and f.path.endswith(fname))

    def test_clean_files_are_silent(self, res):
        noisy = [f.render() for f in res.findings
                 if f.path.endswith(("clean_shapes.py", "pallas_clean.py",
                                     "meshdef.py", "layoutdef.py"))]
        assert noisy == []

    def test_gc040_mesh_axis_divisibility(self, res):
        # dp=4 does not divide the 6 rows imported from meshdef.py —
        # the shape constant resolves cross-file
        assert self._at(res, "bad_shapes.py", "GC040") == [34]

    def test_gc041_sharded_contraction_all_three_forms(self, res):
        # literal P on matmul (42), logical-name literal tuple through
        # spec_for_logical on einsum (49), cross-file SpecLayout table
        # entry (58)
        assert self._at(res, "bad_shapes.py", "GC041") == [42, 49, 58]

    def test_gc042_pallas_block_consistency(self, res):
        # index-map arity (22), index rank (32), mis-bucketed block
        # (44), grid overruns array (55), kernel param count (62)
        assert self._at(res, "pallas_bad.py", "GC042") == \
            [22, 32, 44, 55, 62]

    def test_gc043_codec_pairing(self, res):
        # psum on still-quantized payload (76), unpaired send (82) —
        # both through the (payload, scales) tuple unpack
        assert self._at(res, "bad_shapes.py", "GC043") == [76, 82]

    def test_gc044_collective_geometry(self, res):
        # fires at the psum_scatter line inside the target fn: the
        # per-shard 3 rows are not divisible by tp=2
        assert self._at(res, "bad_shapes.py", "GC044") == [29]

    def test_gc022_is_path_sensitive(self, res):
        # only the except-edge read after the donating call fires; the
        # read-before-donation and rebind forms in clean_shapes.py stay
        # silent (pre-CFG GC022 flagged any later mention)
        assert self._at(res, "bad_shapes.py", "GC022") == [92]

    def test_exactly_the_seeded_positives(self, res):
        assert len(res.findings) == 13 and res.errors == 0

    def test_shape_stats_surface_analysis_cost(self, res):
        st = res.shape_stats
        assert st.get("fns_analyzed", 0) > 0
        assert st.get("pallas_sites", 0) >= 9
        assert st.get("contraction_fns", 0) >= 4
        assert st.get("sites_shaped", 0) >= 5
        assert st.get("fns_nonconverged", 0) == 0


class TestLoweredWrapperResolution:
    """Satellite-2 regressions: GC020/021 must see through the
    lower_shard_map wrapper and through functools.partial(shard_map)
    with keyword-only bound specs."""

    @pytest.fixture(scope="class")
    def res(self):
        return run_pkg("lowered_pkg", rules={"GC020", "GC021", "GC022"})

    def test_wrapper_call_arity_mismatch(self, res):
        hits = [(os.path.basename(f.path), f.line) for f in res.findings
                if f.rule == "GC021"]
        assert ("lowered.py", 17) in hits

    def test_partial_kwonly_specs_resolve(self, res):
        hits = [(os.path.basename(f.path), f.line) for f in res.findings
                if f.rule == "GC021"]
        assert ("partial_specs.py", 27) in hits

    def test_good_forms_stay_silent(self, res):
        # good_wrapper/good_lower_jit/good_partial(_collective) add no
        # noise: exactly the two seeded arity bugs
        assert len(res.findings) == 2


def test_cached_shape_findings_identical_to_cold(tmp_path):
    """Shape facts and GC040-044 findings ride the content-hash cache:
    a warm run reproduces the cold findings and stats byte-for-byte
    without re-running the abstract interpreter."""
    pkg = os.path.join(FIXTURES, "shapes_pkg")
    cache = str(tmp_path / "cache.json")
    cold = check_project([pkg], rules=SHAPES, cache_path=cache,
                         root=FIXTURES)
    warm = check_project([pkg], rules=SHAPES, cache_path=cache,
                         root=FIXTURES)
    assert warm.parsed == 0 and warm.cached == len(warm.files)
    assert [f.render() for f in warm.findings] == \
        [f.render() for f in cold.findings]
    assert warm.findings
    assert warm.shape_stats == cold.shape_stats


def test_sarif_includes_shape_rule_metadata():
    """The SARIF driver carries GC040-044 entries so code-scanning
    renders the shape family."""
    from ray_tpu.devtools.graftcheck.sarif import to_sarif
    from ray_tpu.devtools.graftcheck.local import Finding

    doc = to_sarif([Finding("a.py", 3, 1, "GC040", "indivisible")])
    driver = doc["runs"][0]["tool"]["driver"]
    assert {"GC040", "GC041", "GC042", "GC043", "GC044"} <= \
        {r["id"] for r in driver["rules"]}
    assert doc["runs"][0]["results"][0]["ruleId"] == "GC040"


def test_baseline_round_trips_shape_findings(tmp_path):
    """A baselined GC040 finding is suppressed on re-run and
    resurrects only when its fingerprint changes."""
    from ray_tpu.devtools.graftcheck import baseline

    res = run_pkg("shapes_pkg", rules={"GC040"})
    assert [f.rule for f in res.findings] == ["GC040"]
    bl = str(tmp_path / "bl.json")
    baseline.write(bl, res.findings)
    assert baseline.filter_findings(res.findings, bl) == []


def test_reverse_dependency_closure_follows_importers():
    """--diff scoping: a change to meshdef.py must re-lint every file
    whose cross-file shape facts can see it — but not the pallas
    fixtures, which never import it."""
    res = run_pkg("shapes_pkg", rules=SHAPES)
    mesh = os.path.abspath(
        os.path.join(FIXTURES, "shapes_pkg", "meshdef.py"))
    scope = {os.path.basename(p)
             for p in reverse_dependency_closure(res.index, [mesh])}
    assert {"meshdef.py", "bad_shapes.py", "clean_shapes.py"} <= scope
    assert "pallas_bad.py" not in scope and "pallas_clean.py" not in scope


def test_diff_mode_scopes_cli_reporting(tmp_path, monkeypatch):
    """`graftcheck --diff REF` reports only findings inside the changed
    files' reverse-dependency closure: an unrelated edit passes even
    though the tree still holds a finding elsewhere."""
    import subprocess

    def git(*args):
        subprocess.run(["git", "-C", str(tmp_path), "-c",
                        "user.email=t@t", "-c", "user.name=t", *args],
                       check=True, capture_output=True)

    bad_src = ("import ray_tpu\n"
               "@ray_tpu.remote\n"
               "def f(r):\n"
               "    return ray_tpu.get(r)\n")
    (tmp_path / "bad.py").write_text(bad_src)
    (tmp_path / "other.py").write_text("Y = 1\n")
    git("init", "-q")
    git("add", ".")
    git("commit", "-qm", "base")
    monkeypatch.chdir(tmp_path)
    assert graftcheck.main(["--no-cache", str(tmp_path)]) == 1
    # edit only other.py: the diff closure excludes bad.py -> clean
    (tmp_path / "other.py").write_text("Y = 2\n")
    assert graftcheck.main(["--no-cache", "--diff", "HEAD",
                            str(tmp_path)]) == 0
    # touching bad.py itself brings its finding back into scope
    (tmp_path / "bad.py").write_text(bad_src + "# touched\n")
    assert graftcheck.main(["--no-cache", "--diff", "HEAD",
                            str(tmp_path)]) == 1


def test_library_tree_is_shape_clean(tree_result):
    """Full-tree sweep for the v4 family: zero un-annotated GC040-044
    findings across ray_tpu/ (ops/ pallas kernels, models/, parallel/
    sharding/, serve/llm/), examples/ and tests/."""
    assert _tree_findings(
        tree_result, {"GC040", "GC041", "GC042", "GC043", "GC044"}) == []


def test_flash_attention_pallas_sites_visited_and_clean():
    """GC042's in-repo clean corpus: every pallas_call in ops/ (incl.
    flash_attention's forward/backward kernels) is visited — not
    skipped as unparseable — and produces no findings as-is."""
    res = check_project([os.path.join(REPO, "ray_tpu", "ops")],
                        rules={"GC042"}, cache_path=None)
    assert res.findings == []
    # the five flash kernels (flash_attention.KERNEL_NAMES)
    assert res.shape_stats.get("pallas_sites", 0) >= 5


# ---------------------------------------------------------------------------
# data-feed fixture package (ISSUE 19): feed actor on a cyclic cgraph +
# block-ref lifecycle in the staging tier


class TestDataFeedPack:
    def test_pump_bound_into_cycle_stays_gc008_clean(self):
        """FeedPump.pack / TrainStage.forward/backward are bound into a
        cyclic compiled graph (pump -> s0 -> s1 -> s0) but are pure
        channel dataflow: only the DirtyPump positive control fires."""
        res = run_pkg("data_feed_pkg", rules={"GC008"})
        assert len(res.findings) == 1, res.findings
        f = res.findings[0]
        assert os.path.basename(f.path) == "feed.py"
        assert "DirtyPump" in f.message or f.line == 51

    def test_feed_cycle_is_dataflow_not_gc010_deadlock(self):
        """The pump-on-a-cycle bind shape is channel dataflow — GC010
        flags ONLY the BlockingPump/BlockingSink synchronous wait cycle
        seeded as the positive control."""
        res = run_pkg("data_feed_pkg", rules={"GC010"})
        assert len(res.findings) == 1, res.findings
        msg = res.findings[0].message
        assert "BlockingPump.fill" in msg
        assert "BlockingSink.take" in msg
        assert "FeedPump" not in msg

    def test_block_ref_lifecycle_positives_and_cleans(self):
        """GC030-033 over the staging tier's channel/pool shapes: each
        seeded leak fires with its rule, the shipped try/finally and
        ownership-transfer idioms stay silent."""
        res = run_pkg("data_feed_pkg", rules=LIFECYCLE)
        by_fn = {}
        src = open(os.path.join(FIXTURES, "data_feed_pkg",
                                "blocks.py")).read().splitlines()
        for f in res.findings:
            assert os.path.basename(f.path) == "blocks.py", f.render()
            # attribute each finding to its enclosing def
            fn = next(line.split()[1].split("(")[0]
                      for line in reversed(src[:f.line])
                      if line.startswith("def "))
            by_fn.setdefault(fn, set()).add(f.rule)
        assert "GC030" in by_fn.get("early_return_leak", set())
        assert "GC031" in by_fn.get("double_release", set())
        assert "GC032" in by_fn.get("swallowed_release", set())
        assert "GC033" in by_fn.get("conditional_acquire", set())
        assert "pump_window_clean" not in by_fn
        assert "handoff_clean" not in by_fn


def test_shipped_data_tree_is_clean():
    """ray_tpu/data/ (incl. the new feed.py + executor byte windows)
    sweeps clean under the whole-program + lifecycle families — the
    subsystem the fixture pack models carries no un-annotated
    findings."""
    res = check_project(
        [os.path.join(REPO, "ray_tpu", "data")],
        rules={"GC008", "GC010", "GC011",
               "GC030", "GC031", "GC032", "GC033"},
        cache_path=None, root=os.path.join(REPO, "ray_tpu"))
    assert res.errors == 0
    assert [f.render() for f in res.findings] == []


# ---------------------------------------------------------------------------
# concurrency rules GC050-054 (graftcheck v5): guarded-by inference,
# reentrancy/callback deadlocks, lock-order cycles, blocking-under-lock,
# check-then-act


CONCURRENCY = {"GC050", "GC051", "GC052", "GC053", "GC054"}


class TestConcurrencyFixtures:
    """The concurrency_pkg fixture pack: every seeded positive fires on
    its line, every shipped idiom (with-locks, RLock re-entry through a
    helper, try-acquire probes, Condition-on-own-lock waits, bounded
    gets, constructor escapes) stays silent."""

    @pytest.fixture(scope="class")
    def res(self):
        return run_pkg("concurrency_pkg", rules=CONCURRENCY)

    def _at(self, res, fname, rule):
        return [f for f in res.findings
                if f.path.endswith(fname) and f.rule == rule]

    def test_clean_idioms_are_silent(self, res):
        assert not any(f.path.endswith("clean.py") for f in res.findings)

    def test_unlocked_write_to_guarded_attr_is_gc050(self, res):
        hits = self._at(res, "guarded.py", "GC050")
        assert [f.line for f in hits] == [26]
        msg = hits[0].message
        assert "_table" in msg and "self._lock" in msg
        assert "3/4" in msg     # inference ratio surfaces in the report

    def test_direct_reacquire_through_helper_is_gc051(self, res):
        """kick() -> _drain() re-acquires the non-reentrant lock: the
        helper pass pushes kick's held set into _drain, which reports
        the re-acquire on its with-line; the transitive project rule
        additionally names the call site."""
        hits = self._at(res, "reentry.py", "GC051")
        direct = [f for f in hits if f.line == 34]
        assert direct and "re-acquiring non-reentrant" in direct[0].message
        trans = [f for f in hits if f.line == 31]
        assert trans and "transitively" in trans[0].message

    def test_callback_under_lock_via_helper_hop_is_gc051(self, res):
        """publish() holds the lock and calls _emit(), which invokes the
        stored subscriber callbacks: the held set crosses the helper hop
        and the invocation line fires."""
        cb = [f for f in self._at(res, "reentry.py", "GC051")
              if f.line == 27]
        assert len(cb) == 1 and "callback" in cb[0].message
        assert "self._lock" in cb[0].message

    def test_rlock_twin_stays_silent(self, res):
        # ReentrantDispatcher (line 37 on) mirrors kick/_drain on an
        # RLock: zero findings there
        assert all(f.line < 37 for f in self._at(res, "reentry.py",
                                                 "GC051"))

    def test_three_class_order_cycle_is_gc052(self, res):
        hits = self._at(res, "ordering.py", "GC052")
        assert len(hits) == 1
        msg = hits[0].message
        for cls in ("Alpha._lock", "Beta._lock", "Gamma._lock"):
            assert cls in msg
        # every hop carries its file:line witness
        for line in (20, 30, 43):
            assert f"ordering.py:{line}" in msg, (line, msg)

    def test_order_cycle_is_not_a_gc051_self_deadlock(self, res):
        # each hop re-enters a DIFFERENT instance's lock: order hazard,
        # not a self-deadlock — GC051 must stay quiet in ordering.py
        assert self._at(res, "ordering.py", "GC051") == []

    def test_blocking_under_lock_is_gc053(self, res):
        hits = self._at(res, "blocking.py", "GC053")
        assert [f.line for f in hits] == [22, 28]
        assert "Queue.get() with no timeout" in hits[0].message
        assert "join()" in hits[1].message

    def test_check_then_act_is_gc054(self, res):
        hits = self._at(res, "checkact.py", "GC054")
        assert [f.line for f in hits] == [19, 29]
        member = hits[0].message
        assert "membership tested at line 17" in member
        assert "released in between" in member
        event = hits[1].message
        assert "is_set()" in event and "line 28" in event

    def test_exactly_the_seeded_positives(self, res):
        expect = {("blocking.py", 22, "GC053"),
                  ("blocking.py", 28, "GC053"),
                  ("checkact.py", 19, "GC054"),
                  # the dropped-lock pop is ALSO an unguarded write to a
                  # majority-guarded attr: both rules own that line
                  ("checkact.py", 19, "GC050"),
                  ("checkact.py", 29, "GC054"),
                  ("guarded.py", 26, "GC050"),
                  ("ordering.py", 20, "GC052"),
                  ("reentry.py", 27, "GC051"),
                  ("reentry.py", 31, "GC051"),
                  ("reentry.py", 34, "GC051")}
        got = {(os.path.basename(f.path), f.line, f.rule)
               for f in res.findings}
        assert got == expect, got.symmetric_difference(expect)
        assert res.errors == 0

    def test_concurrency_stats_surface_analysis_cost(self, res):
        st = res.concurrency_stats
        assert st.get("fns_analyzed", 0) > 0
        assert st.get("classes_with_locks", 0) >= 10
        assert st.get("guards_inferred", 0) >= 3
        assert st.get("helper_reruns", 0) >= 1
        assert st.get("fns_errors", 0) == 0


def test_cached_concurrency_findings_identical_to_cold(tmp_path):
    """Lock tables, held-call facts and GC050-054 findings ride the
    content-hash cache: a warm run reproduces the cold findings and
    stats byte-for-byte without re-running the lock-domain fixpoint."""
    pkg = os.path.join(FIXTURES, "concurrency_pkg")
    cache = str(tmp_path / "cache.json")
    cold = check_project([pkg], rules=CONCURRENCY, cache_path=cache,
                         root=FIXTURES)
    warm = check_project([pkg], rules=CONCURRENCY, cache_path=cache,
                         root=FIXTURES)
    assert warm.parsed == 0 and warm.cached == len(warm.files)
    assert [f.render() for f in warm.findings] == \
        [f.render() for f in cold.findings]
    assert warm.findings
    assert warm.concurrency_stats == cold.concurrency_stats


def test_sarif_includes_concurrency_rule_metadata():
    """The v5 SARIF driver carries GC050-054 entries and the bumped
    tool version so code-scanning renders the new family."""
    from ray_tpu.devtools.graftcheck.sarif import to_sarif
    from ray_tpu.devtools.graftcheck.local import Finding

    doc = to_sarif([Finding("a.py", 3, 1, "GC050", "unguarded")])
    driver = doc["runs"][0]["tool"]["driver"]
    assert driver["version"] == "5.0.0"
    assert {"GC050", "GC051", "GC052", "GC053", "GC054"} <= \
        {r["id"] for r in driver["rules"]}
    assert doc["runs"][0]["results"][0]["ruleId"] == "GC050"


def test_baseline_round_trips_concurrency_findings(tmp_path):
    """A baselined GC050 finding is suppressed on re-run."""
    from ray_tpu.devtools.graftcheck import baseline

    res = run_pkg("concurrency_pkg", rules={"GC050"})
    assert {f.rule for f in res.findings} == {"GC050"}
    bl = str(tmp_path / "bl.json")
    baseline.write(bl, res.findings)
    assert baseline.filter_findings(res.findings, bl) == []


def test_diff_mode_scopes_concurrency_reporting(tmp_path, monkeypatch):
    """GC050 rides --diff scoping: an edit away from the offending
    class passes, touching the class brings its finding into scope."""
    import subprocess

    def git(*args):
        subprocess.run(["git", "-C", str(tmp_path), "-c",
                        "user.email=t@t", "-c", "user.name=t", *args],
                       check=True, capture_output=True)

    bad_src = (
        "import threading\n"
        "class C:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._d = {}\n"
        "    def a(self):\n"
        "        with self._lock:\n"
        "            self._d['k'] = 1\n"
        "    def b(self):\n"
        "        with self._lock:\n"
        "            return self._d.get('k')\n"
        "    def c(self):\n"
        "        with self._lock:\n"
        "            return len(self._d)\n"
        "    def d(self):\n"
        "        self._d.pop('k', None)\n")
    (tmp_path / "bad.py").write_text(bad_src)
    (tmp_path / "other.py").write_text("Y = 1\n")
    git("init", "-q")
    git("add", ".")
    git("commit", "-qm", "base")
    monkeypatch.chdir(tmp_path)
    assert graftcheck.main(["--no-cache", "--rules", "GC050",
                            str(tmp_path)]) == 1
    (tmp_path / "other.py").write_text("Y = 2\n")
    assert graftcheck.main(["--no-cache", "--rules", "GC050", "--diff",
                            "HEAD", str(tmp_path)]) == 0
    (tmp_path / "bad.py").write_text(bad_src + "# touched\n")
    assert graftcheck.main(["--no-cache", "--rules", "GC050", "--diff",
                            "HEAD", str(tmp_path)]) == 1


def test_locks_cli_dot_json_and_text(tmp_path, capsys):
    """`graftcheck locks` renders the static lock-order graph: DOT with
    labeled witness edges, JSON with src/dst/path/line/via records, and
    the default text listing."""
    pkg = os.path.join(FIXTURES, "concurrency_pkg")
    out = tmp_path / "locks.dot"
    rc = graftcheck.main(["locks", "--no-cache", "--dot", "--out",
                          str(out), pkg])
    assert rc == 0
    dot = out.read_text()
    assert dot.startswith("digraph lock_order")
    assert "Alpha._lock" in dot and "Beta._lock" in dot
    assert "ordering.py:" in dot      # witness file:line on the edge label

    jout = tmp_path / "locks.json"
    rc = graftcheck.main(["locks", "--no-cache", "--json", "--out",
                          str(jout), pkg])
    assert rc == 0
    doc = json.loads(jout.read_text())
    assert doc["edges"], "expected order edges"
    for e in doc["edges"]:
        assert {"src", "dst", "path", "line", "via"} <= set(e)
    srcs = {e["src"] for e in doc["edges"]}
    assert any("Alpha._lock" in s for s in srcs)

    rc = graftcheck.main(["locks", "--no-cache", pkg])
    assert rc == 0
    text = capsys.readouterr().out
    assert "->" in text and "order edges" in text


def test_library_tree_is_concurrency_clean(tree_result):
    """Full-tree sweep for the v5 family: zero un-annotated GC050-054
    findings across ray_tpu/, examples/ and tests/ — and the analyzer
    ran everywhere it should (silent per-function failures would make
    the sweep vacuously clean)."""
    assert _tree_findings(
        tree_result,
        {"GC050", "GC051", "GC052", "GC053", "GC054"}) == []
    st = tree_result.concurrency_stats
    assert st.get("fns_analyzed", 0) > 500
    assert st.get("classes_with_locks", 0) >= 40
    assert st.get("guards_inferred", 0) >= 50
    assert st.get("fns_errors", 0) == 0
