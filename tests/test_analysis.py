"""repro.analysis: lint rules (positive/negative/waiver per rule), the
waiver grammar, strict gating on the real tree, and the jaxpr contract
auditor against the real train step + fused builder (interpret mode)."""
import json
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.analysis.config import AnalysisConfig
from repro.analysis.lint import LintReport, lint_paths, lint_source, \
    parse_waivers
from repro.analysis.rules import RULES

SRC = Path(__file__).resolve().parent.parent / "src"


def rules_hit(source, relpath, config=None):
    return {v.rule for v in lint_source(source, relpath, config)
            if not v.waived}


# ---------------------------------------------------------------------------
# per-rule fixtures: positive + negative + waiver
# ---------------------------------------------------------------------------
class TestNoGlobalNumpyRandom:
    def test_positive_seed_and_module_fns(self):
        src = ("import numpy as np\n"
               "np.random.seed(0)\n"
               "x = np.random.rand(3)\n")
        vs = [v for v in lint_source(src, "repro/core/foo.py")
              if v.rule == "no-global-numpy-random"]
        assert {v.line for v in vs} == {2, 3}

    def test_negative_generator_constructors(self):
        src = ("import numpy as np\n"
               "rng = np.random.default_rng((1, 2))\n"
               "ss = np.random.SeedSequence(7)\n")
        assert "no-global-numpy-random" not in rules_hit(
            src, "repro/core/foo.py")

    def test_waiver(self):
        src = ("import numpy as np\n"
               "np.random.seed(0)  # analysis: allow[no-global-numpy-random] -- fixture\n")
        (v,) = [v for v in lint_source(src, "repro/core/foo.py")
                if v.rule == "no-global-numpy-random"]
        assert v.waived and v.justification == "fixture"


class TestNoStdlibRandom:
    def test_positive(self):
        assert "no-stdlib-random" in rules_hit(
            "import random\n", "repro/core/foo.py")
        assert "no-stdlib-random" in rules_hit(
            "from random import shuffle\n", "repro/core/foo.py")

    def test_negative(self):
        assert "no-stdlib-random" not in rules_hit(
            "import numpy as np\n", "repro/core/foo.py")

    def test_waiver(self):
        src = ("# analysis: allow[no-stdlib-random] -- fixture only\n"
               "import random\n")
        (v,) = lint_source(src, "repro/core/foo.py")
        assert v.waived


class TestNoWallClock:
    SRC = "import time\nt = time.time()\nm = time.monotonic()\n"

    def test_positive_in_deterministic_module(self):
        vs = [v for v in lint_source(self.SRC, "repro/pipeline/foo.py")
              if v.rule == "no-wall-clock"]
        assert {v.line for v in vs} == {2, 3}

    def test_datetime_now(self):
        src = "from datetime import datetime\nd = datetime.now()\n"
        assert "no-wall-clock" in rules_hit(src, "repro/batching/foo.py")

    def test_negative_outside_deterministic_scope(self):
        # wall clock is FINE in the trainer/bench layer
        assert "no-wall-clock" not in rules_hit(self.SRC,
                                                "repro/train/foo.py")

    def test_waiver(self):
        src = ("import time\n"
               "t = time.monotonic()  # analysis: allow[no-wall-clock] -- heartbeat\n")
        (v,) = [v for v in lint_source(src, "repro/pipeline/foo.py")
                if v.rule == "no-wall-clock"]
        assert v.waived and v.justification == "heartbeat"


HOT_CFG = AnalysisConfig(
    hot_functions={"repro/pipeline/hot.py": ("hot_fn",),
                   "repro/kernels/k.py": ("*",)})


class TestNoHostSyncInHotPath:
    def test_positive_float_item_asarray(self):
        src = ("import numpy as np\n"
               "def hot_fn(x):\n"
               "    a = float(x)\n"
               "    b = x.item()\n"
               "    c = np.asarray(x)\n"
               "    return a, b, c\n")
        vs = [v for v in lint_source(src, "repro/pipeline/hot.py", HOT_CFG)
              if v.rule == "no-host-sync-in-hot-path"]
        assert {v.line for v in vs} == {3, 4, 5}

    def test_negative_outside_hot_function(self):
        src = ("def cold_fn(x):\n"
               "    return float(x)\n")
        assert not rules_hit(src, "repro/pipeline/hot.py", HOT_CFG)

    def test_negative_literal_argument(self):
        # float('inf') etc: constant folding, not a device sync
        src = ("def hot_fn(x):\n"
               "    return float('inf')\n")
        assert not rules_hit(src, "repro/pipeline/hot.py", HOT_CFG)

    def test_star_marks_whole_module(self):
        src = ("import jax\n"
               "def anything(x):\n"
               "    return jax.device_get(x)\n")
        assert "no-host-sync-in-hot-path" in rules_hit(
            src, "repro/kernels/k.py", HOT_CFG)

    def test_waiver(self):
        src = ("def hot_fn(x):\n"
               "    # analysis: allow[no-host-sync-in-hot-path] -- boundary flush\n"
               "    return float(x)\n")
        (v,) = lint_source(src, "repro/pipeline/hot.py", HOT_CFG)
        assert v.waived and v.justification == "boundary flush"


class TestNoF64InDeviceCode:
    def test_positive(self):
        src = ("import jax.numpy as jnp\n"
               "x = jnp.zeros(3, jnp.float64)\n"
               "y = x.astype('float64')\n")
        vs = [v for v in lint_source(src, "repro/kernels/foo.py")
              if v.rule == "no-f64-in-device-code"]
        assert {v.line for v in vs} == {2, 3}

    def test_negative_host_exempt_module(self):
        # featcache/plan.py computes f64 scores on host and casts at the
        # device boundary — exempt via config
        src = "import numpy as np\ns = np.float64(1.0)\n"
        assert not rules_hit(src, "repro/featcache/plan.py")

    def test_negative_non_device_module(self):
        src = "import numpy as np\ns = np.float64(1.0)\n"
        assert not rules_hit(src, "repro/core/community.py")


class TestRngStructuredSeed:
    def test_positive_bare_int_and_entropy(self):
        src = ("import numpy as np\n"
               "a = np.random.default_rng(5)\n"
               "b = np.random.default_rng()\n")
        vs = [v for v in lint_source(src, "repro/core/foo.py")
              if v.rule == "rng-structured-seed"]
        assert {v.line for v in vs} == {2, 3}

    def test_negative_tuple_seed(self):
        src = ("import numpy as np\n"
               "a = np.random.default_rng((5, 0))\n"
               "b = np.random.default_rng((seed, epoch, pos))\n")
        assert "rng-structured-seed" not in rules_hit(src,
                                                      "repro/core/foo.py")


class TestNoDeprecatedImport:
    def test_positive(self):
        assert "no-deprecated-import" in rules_hit(
            "from repro.core.cachesim import lru_misses\n",
            "repro/featcache/foo.py")
        assert "no-deprecated-import" in rules_hit(
            "import repro.core.sampler\n", "repro/sampling/foo.py")
        assert "no-deprecated-import" in rules_hit(
            "from repro.core import cachesim\n", "repro/featcache/foo.py")

    def test_negative_replacement_and_shim_itself(self):
        assert "no-deprecated-import" not in rules_hit(
            "from repro.featcache import sim\n", "repro/featcache/foo.py")
        # the shim module re-exporting is not a violation of itself
        assert "no-deprecated-import" not in rules_hit(
            "from repro.featcache.sim import *\n",
            "repro/core/cachesim.py")


# ---------------------------------------------------------------------------
# waiver grammar + strict gating
# ---------------------------------------------------------------------------
class TestWaivers:
    def test_parse_same_line_and_line_above(self):
        src = ("x = 1  # analysis: allow[rule-a] -- because reasons\n"
               "# analysis: allow[rule-b] -- next line covered\n"
               "y = 2\n")
        w = parse_waivers(src)
        assert w[(1, "rule-a")] == "because reasons"
        assert w[(3, "rule-b")] == "next line covered"

    def test_unjustified_waiver_fails_strict(self):
        src = ("import random  # analysis: allow[no-stdlib-random]\n")
        vs = lint_source(src, "repro/core/foo.py")
        rep = LintReport(violations=vs, files_checked=1)
        assert vs[0].waived and not rep.strict_ok()

    def test_wrong_rule_name_does_not_waive(self):
        src = ("import random  # analysis: allow[no-wall-clock] -- wrong\n")
        (v,) = lint_source(src, "repro/core/foo.py")
        assert not v.waived


def test_repo_is_strict_clean():
    """The acceptance gate: zero unwaived violations across src/repro
    and every waiver names a known rule and carries a justification."""
    report = lint_paths(SRC)
    assert report.files_checked > 90
    msgs = [f"{v.path}:{v.line} [{v.rule}] {v.message}"
            for v in report.unwaived]
    assert not msgs, "\n".join(msgs)
    assert not report.unjustified()
    assert not report.unknown_waivers
    # the audited waivers documented in the PR are present
    waived_files = {v.path for v in report.waived}
    assert "repro/pipeline/prefetch.py" in waived_files
    assert "repro/train/gnn_loop.py" in waived_files


def test_no_internal_deprecated_importers():
    """Satellite: no src/repro module imports the deprecation shims."""
    report = lint_paths(SRC)
    dep = [v for v in report.violations
           if v.rule == "no-deprecated-import"]
    assert dep == []


def test_rule_registry_complete():
    assert set(RULES) == {
        "no-global-numpy-random", "no-stdlib-random", "no-wall-clock",
        "no-host-sync-in-hot-path", "no-f64-in-device-code",
        "rng-structured-seed", "no-deprecated-import"}


# ---------------------------------------------------------------------------
# jaxpr contract auditor
# ---------------------------------------------------------------------------
from repro.analysis import jaxpr_audit as ja  # noqa: E402


def test_donation_effective():
    assert ja.audit_donation()["ok"]


def test_kernels_pallas_contract():
    rep = ja.audit_kernels()
    for name in ("gather_agg_fwd", "gather_agg_bwd",
                 "gather_cached_fwd", "gather_cached_bwd"):
        r = rep[name]
        assert r["pallas_calls"] >= 1, (name, r)
        assert r["callbacks"] == 0 and r["f64_casts"] == 0, (name, r)
        assert r["feature_gathers"] == 0, (name, r)
    assert rep["ok"]


def test_feature_gather_detector_flags_reference_impl():
    """The detector must actually fire on the materialized fallback —
    the jnp reference path gathers feature-shaped rows."""
    from repro.kernels.gather_agg.ops import gather_agg
    x = jnp.ones((64, 32), jnp.float32)
    idx = jnp.zeros((16, 4), jnp.int32)
    w = jnp.ones((16, 4), jnp.float32)
    closed = jax.make_jaxpr(
        lambda x, idx, w: gather_agg(x, idx, w, impl="jnp"))(x, idx, w)
    assert len(ja.feature_gathers(closed, 32)) >= 1


def test_device_order_audit(tiny_graph):
    rep = ja.audit_device_order(tiny_graph)
    for pol in ("rand", "norand", "comm_rand", "clustergcn", "labor"):
        assert rep[pol]["stable"], (pol, rep[pol])
        assert rep[pol]["ok"], (pol, rep[pol])
    assert rep["ok"]


def test_fused_build_audit(tiny_graph):
    """Jaxpr hash identical across (pos, epoch, resume) for all five
    policies: the fused builder never retraces within a run."""
    rep = ja.audit_fused_build(tiny_graph)
    for pol in ("rand", "norand", "comm_rand", "clustergcn", "labor"):
        r = rep[pol]
        assert r["stable"] and r["callbacks"] == 0 and \
            r["f64_casts"] == 0 and r["f64_avals"] == 0, (pol, r)
    assert rep["ok"]


def test_train_step_audit(tiny_graph):
    """The guarded train step: callback-free, f64-free, hash-stable
    across poison/lr/key/batch/resume, Pallas path declared -> present."""
    rep = ja.audit_train_step(tiny_graph)
    assert rep["callbacks"] == 0
    assert rep["f64_casts"] == 0 and rep["f64_avals"] == 0
    assert rep["stable"], rep
    assert rep["pallas"]["pallas_calls"] >= 1
    assert rep["eval"]["ok"]
    assert rep["ok"]


def test_recompile_guard_catches_tracer_constant():
    """Pinned regression: a weak-typed python scalar CAPTURED in the
    closure embeds as a jaxpr literal — the hash must drift (that is the
    silent-retrace bug class). The same scalar passed as an ARGUMENT
    must not."""
    x = jnp.ones((4,), jnp.float32)

    def make_step(scale):
        def step(x):
            return x * scale        # captured: becomes a literal
        return step

    h_captured = [ja.make_hash(make_step(s), x) for s in (1.5, 2.5)]
    assert h_captured[0] != h_captured[1]

    def step_arg(x, scale):
        return x * scale            # argument: traced, value-free

    h_arg = [ja.make_hash(step_arg, x, s) for s in (1.5, 2.5)]
    assert h_arg[0] == h_arg[1]
    # and the poison scalar in the real step rides as an argument: the
    # full train-step audit above proves nan vs 1.0 never retraces


def test_callback_detector_fires():
    """The callback check is not vacuous: a deliberate pure_callback is
    found through the pjit wrapper."""
    import numpy as np

    @jax.jit
    def bad(x):
        return jax.pure_callback(
            lambda v: np.asarray(v), jax.ShapeDtypeStruct(x.shape, x.dtype),
            x)

    closed = jax.make_jaxpr(bad)(jnp.ones(3))
    assert ja.callback_eqns(closed)


def test_f64_detector_fires():
    # x64 must be on for a true f64 cast to exist at all (the default
    # config truncates to f32 — itself part of the no-f64 posture); the
    # context keeps the widening strictly inside this test
    with jax.enable_x64(True):
        closed = jax.make_jaxpr(
            lambda x: x.astype("float64"))(jnp.ones(3, jnp.float32))
    assert ja.f64_casts(closed) or ja.f64_avals(closed)


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------
def test_cli_lint_only(tmp_path):
    out = tmp_path / "report.json"
    proc = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--strict",
         "--skip-jaxpr", "--json", str(out)],
        capture_output=True, text=True, cwd=str(SRC.parent),
        env={"PYTHONPATH": str(SRC), "PATH": "/usr/bin:/bin",
             "JAX_PLATFORMS": "cpu",
             "HOME": str(tmp_path)})
    assert proc.returncode == 0, proc.stdout + proc.stderr
    rep = json.loads(out.read_text())
    assert rep["strict_ok"]
    assert rep["lint"]["files_checked"] > 90
    assert rep["lint"]["n_violations"] == 0
    assert rep["lint"]["n_waived"] > 0
    # every waiver in the report carries its justification
    for rule, entry in rep["lint"]["rules"].items():
        for w in entry["waivers"]:
            assert w["justification"], (rule, w)


def test_sharded_step_audit(tiny_graph):
    """The data-parallel (shard_map) step under the same contract:
    callback-free, f64-free, grads psum-reduced, donation aliased in the
    lowering, ONE jaxpr hash across poison/lr/key/batch/fresh-trainer."""
    rep = ja.audit_sharded_step(tiny_graph)
    assert rep["callbacks"] == 0
    assert rep["f64_casts"] == 0 and rep["f64_avals"] == 0
    assert rep["stable"], rep
    assert rep["spmd"] and rep["n_devices"] == 1
    assert rep["psums"] >= 1            # grads + loss + mask count
    assert rep["halo_plan"]["mode"] in ("halo", "global")
    assert rep["halo_plan"]["halo"] == 0      # 1-device ring
    assert rep["donation_aliased"]
    assert rep["ok"]
