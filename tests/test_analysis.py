"""tools/analysis — fixture snippets per rule (positive, negative,
suppressed), the baseline ratchet, the CLI contract, and the repo-wide
green guarantee `make analyze` enforces.

Runs in the default (not slow) lane: pure AST work, no jax imports by the
analyzer itself.
"""
import json
import subprocess
import sys
from pathlib import Path

import pytest

from tools.analysis import analyze_paths, load_baseline
from tools.analysis.core import RULES, write_baseline

REPO = Path(__file__).resolve().parent.parent


def findings_for(tmp_path, source, name="snippet.py"):
    path = tmp_path / name
    path.write_text(source)
    return analyze_paths([str(path)]).findings


def rule_ids(findings):
    return sorted(f.rule for f in findings)


# ---------------------------------------------------------------------------
# CSA1xx trace-safety
# ---------------------------------------------------------------------------

def test_trace_safety_flags_control_flow_and_casts(tmp_path):
    src = (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    if x > 0:\n"
        "        x = x + 1\n"
        "    while x < 3:\n"
        "        x = x * 2\n"
        "    y = jnp.sum(x)\n"
        "    return int(y)\n"
    )
    got = rule_ids(findings_for(tmp_path, src))
    assert got == ["CSA101", "CSA101", "CSA102"]


def test_trace_safety_scans_transitive_callees(tmp_path):
    # the jitted fn is clean; the plain helper it calls is not
    src = (
        "import jax\n"
        "def helper(y):\n"
        "    return bool(y)\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    return helper(x)\n"
    )
    found = findings_for(tmp_path, src)
    assert rule_ids(found) == ["CSA102"]
    assert found[0].context == "helper"


def test_trace_safety_negative_static_and_shape(tmp_path):
    # static args, shape reads, and host-annotated callee params are not
    # tracers; partial(jax.jit, static_argnums) form must be understood
    src = (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "from functools import partial\n"
        "def pick(n: int):\n"
        "    if n > 2:\n"
        "        return 1\n"
        "    return 0\n"
        "@partial(jax.jit, static_argnums=(0,))\n"
        "def f(cfg, x):\n"
        "    if cfg.wide:\n"
        "        x = x + 1\n"
        "    n = x.shape[0]\n"
        "    if n > 2:\n"
        "        x = x * 2\n"
        "    return x + pick(int(n))\n"
    )
    assert findings_for(tmp_path, src) == []


def test_trace_safety_jit_factory_form(tmp_path):
    # a def passed by name into a jit-memoizing factory (the
    # utils/ssz/bulk.py `_get_root_jit(name, fn)` shape) is jit context
    src = (
        "import jax\n"
        "_memo = {}\n"
        "def get_jit(name, fn):\n"
        "    if name not in _memo:\n"
        "        _memo[name] = jax.jit(fn)\n"
        "    return _memo[name]\n"
        "def root(x):\n"
        "    return int(x)\n"
        "def driver(x):\n"
        "    return get_jit('root', root)(x)\n"
    )
    found = findings_for(tmp_path, src)
    assert rule_ids(found) == ["CSA102"]
    assert found[0].context == "root"


def test_trace_safety_wrapper_assignment_form(tmp_path):
    # name = jax.jit(fn): fn is jit context even without a decorator
    src = (
        "import jax\n"
        "def g(x):\n"
        "    return x.item()\n"
        "g_jit = jax.jit(g)\n"
    )
    assert rule_ids(findings_for(tmp_path, src)) == ["CSA102"]


# ---------------------------------------------------------------------------
# CSA2xx dtype-width
# ---------------------------------------------------------------------------

def test_dtype_width_flags_defaulting_ctor_and_wide_literal(tmp_path):
    src = (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "@jax.jit\n"
        "def f(v):\n"
        "    z = jnp.zeros(4)\n"
        "    return z + v * 2 ** 40\n"
    )
    assert rule_ids(findings_for(tmp_path, src)) == ["CSA201", "CSA202"]


def test_dtype_width_negative_explicit_dtype(tmp_path):
    src = (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "@jax.jit\n"
        "def f(v):\n"
        "    z = jnp.zeros(4, dtype=jnp.uint64)\n"
        "    w = jnp.asarray(v)\n"          # copy ctor keeps dtype: fine
        "    return z + w * jnp.uint64(2 ** 40)\n"
    )
    assert findings_for(tmp_path, src) == []


# ---------------------------------------------------------------------------
# CSA3xx purity
# ---------------------------------------------------------------------------

def test_purity_flags_time_random_global_and_mutation(tmp_path):
    src = (
        "import jax, time, random\n"
        "import numpy as np\n"
        "COUNTER = 0\n"
        "@jax.jit\n"
        "def f(x, out):\n"
        "    global COUNTER\n"
        "    t = time.time()\n"
        "    r = random.random()\n"
        "    s = np.random.rand()\n"
        "    out[0] = t + r + s\n"
        "    return x\n"
    )
    got = rule_ids(findings_for(tmp_path, src))
    assert got == ["CSA301", "CSA301", "CSA301", "CSA302", "CSA303"]


def test_purity_negative_host_code_untouched(tmp_path):
    # the same calls OUTSIDE jit context are host code, perfectly legal
    src = (
        "import time\n"
        "def bench():\n"
        "    t0 = time.perf_counter()\n"
        "    return time.perf_counter() - t0\n"
    )
    assert findings_for(tmp_path, src) == []


# ---------------------------------------------------------------------------
# CSA401 state-aliasing
# ---------------------------------------------------------------------------

PRE_FIX_RESIDENT_SNIPPET = (
    # the exact shape of the pre-fix resident.py _install overrides: a
    # `state`-accepting closure answering from captured mirrors
    "import numpy as np\n"
    "class ResidentCore:\n"
    "    def _install(self):\n"
    "        mirrors = self.mirrors\n"
    "        def get_total_balance(state, indices):\n"
    "            idx = np.fromiter(indices, dtype=np.int64)\n"
    "            return max(int(mirrors['effective_balance'][idx].sum()), 1)\n"
    "        def effective_balance_of(state, index):\n"
    "            return int(mirrors['effective_balance'][index])\n"
    "        return get_total_balance, effective_balance_of\n"
)


def test_state_aliasing_flags_pre_fix_resident_pattern(tmp_path):
    found = findings_for(tmp_path, PRE_FIX_RESIDENT_SNIPPET)
    assert rule_ids(found) == ["CSA401", "CSA401"]
    # context is scope-qualified so same-named closures elsewhere in the
    # file can't share a fingerprint
    assert {f.context for f in found} == \
        {"ResidentCore._install.get_total_balance",
         "ResidentCore._install.effective_balance_of"}


def test_state_aliasing_same_named_closures_get_distinct_fingerprints(
        tmp_path):
    src = (
        "class A:\n"
        "    def make(self):\n"
        "        def handler(state, x):\n"
        "            return x\n"
        "        return handler\n"
        "class B:\n"
        "    def make(self):\n"
        "        def handler(state, x):\n"
        "            return x + 1\n"
        "        return handler\n"
    )
    found = findings_for(tmp_path, src)
    assert rule_ids(found) == ["CSA401", "CSA401"]
    fps = {f.fingerprint() for f in found}
    assert len(fps) == 2   # baselining one must not hide the other


def test_state_aliasing_negative_guarded_override(tmp_path):
    # the post-fix shape: delegating on `state is not self.state` reads
    # the parameter, so the aliasing hazard is structurally gone
    src = (
        "class Core:\n"
        "    def _install(self, saved):\n"
        "        def effective_balance_of(state, index):\n"
        "            if state is not self.state:\n"
        "                return saved(state, index)\n"
        "            return int(self.mirrors['effective_balance'][index])\n"
        "        return effective_balance_of\n"
    )
    assert findings_for(tmp_path, src) == []


def test_state_aliasing_skips_stubs_and_honors_suppression(tmp_path):
    src = (
        "def abstract_handler(state, msg):\n"
        "    raise NotImplementedError\n"
        "# csa: ignore[CSA401]\n"
        "def interface_conformance(state, x):\n"
        "    return x\n"
    )
    path = tmp_path / "s.py"
    path.write_text(src)
    report = analyze_paths([str(path)])
    assert report.findings == []
    assert len(report.suppressed) == 1
    assert report.suppressed[0].rule == "CSA401"


# ---------------------------------------------------------------------------
# CSA5xx jit-cache hygiene
# ---------------------------------------------------------------------------

def test_jit_cache_flags_scalar_call_and_unhashable_static(tmp_path):
    src = (
        "import jax\n"
        "from functools import partial\n"
        "def f(n, x):\n"
        "    return x\n"
        "f_jit = jax.jit(f)\n"
        "@partial(jax.jit, static_argnums=(0,))\n"
        "def g(table: list, x):\n"
        "    return x\n"
        "def driver(x):\n"
        "    return f_jit(3, x)\n"
    )
    assert rule_ids(findings_for(tmp_path, src)) == ["CSA501", "CSA502"]


def test_jit_cache_ignores_same_named_attribute_calls(tmp_path):
    # store.update(...) is some other object's method, not the module's
    # jitted `update` — no CSA501
    src = (
        "import jax\n"
        "def _update(n, x):\n"
        "    return x\n"
        "update = jax.jit(_update)\n"
        "def driver(store, x):\n"
        "    store.update(3, x)\n"
        "    return update(x, x)\n"
    )
    assert findings_for(tmp_path, src) == []


def test_trace_safety_walrus_taint(tmp_path):
    # NamedExpr binds like an Assign: both the `if` test containing the
    # walrus and later host casts of its target are traced hazards
    src = (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    if (s := jnp.sum(x)) > 0:\n"
        "        return int(s)\n"
        "    return s\n"
    )
    assert rule_ids(findings_for(tmp_path, src)) == ["CSA101", "CSA102"]


def test_jit_cache_negative_static_scalar_ok(tmp_path):
    # a scalar into a STATIC slot is the intended use; arrays into traced
    # slots are fine too
    src = (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "from functools import partial\n"
        "@partial(jax.jit, static_argnums=(0,))\n"
        "def f(n: int, x):\n"
        "    return x * n\n"
        "def driver(x):\n"
        "    return f(3, jnp.asarray(x))\n"
    )
    assert findings_for(tmp_path, src) == []


# ---------------------------------------------------------------------------
# call-graph IR: cross-module jit context (tools/analysis/callgraph.py)
# ---------------------------------------------------------------------------

def _write_pkg(tmp_path, files):
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    for name, src in files.items():
        (pkg / name).write_text(src)
    return tmp_path


def test_callgraph_taint_crosses_from_import(tmp_path):
    # PR 1 stopped at the file edge: the helper was analyzed as host code
    root = _write_pkg(tmp_path, {
        "helpers.py": "def helper(y):\n    return int(y)\n",
        "main.py": ("import jax\nfrom .helpers import helper\n"
                    "@jax.jit\ndef f(x):\n    return helper(x)\n"),
    })
    found = findings_for_dir(root)
    assert rule_ids(found) == ["CSA102"]
    assert found[0].path.endswith("helpers.py")
    assert found[0].context == "helper"


def test_callgraph_taint_crosses_module_attribute_calls(tmp_path):
    root = _write_pkg(tmp_path, {
        "helpers.py": "def helper(y):\n    return bool(y)\n",
        "main.py": ("import jax\nfrom . import helpers\n"
                    "@jax.jit\ndef f(x):\n    return helpers.helper(x)\n"),
    })
    found = findings_for_dir(root)
    assert rule_ids(found) == ["CSA102"]
    assert found[0].path.endswith("helpers.py")


def test_callgraph_imported_jitted_name_feeds_csa501(tmp_path):
    # `from .kern import f_jit` call sites are CSA5xx-visible now
    root = _write_pkg(tmp_path, {
        "kern.py": ("import jax\ndef _f(x):\n    return x\n"
                    "f_jit = jax.jit(_f)\n"),
        "drv.py": ("from .kern import f_jit\n"
                   "def run():\n    return f_jit(3)\n"),
    })
    found = findings_for_dir(root)
    assert rule_ids(found) == ["CSA501"]
    assert found[0].path.endswith("drv.py")


def test_callgraph_host_annotations_stay_host_cross_module(tmp_path):
    # np.ndarray params are trace-time constants (the fq_tower static
    # int-matrix idiom); `x is None` is an identity check, never a
    # tracer bool — neither may fire CSA101/102 through the call graph
    root = _write_pkg(tmp_path, {
        "helpers.py": ("import numpy as np\n"
                       "def unroll(mat: np.ndarray, x, acc=None):\n"
                       "    for r in range(mat.shape[0]):\n"
                       "        v = int(mat[r, 0])\n"
                       "        if v != 0:\n"
                       "            acc = x if acc is None else acc + x\n"
                       "    return acc\n"),
        "main.py": ("import jax\nfrom .helpers import unroll\n"
                    "@jax.jit\ndef f(mat, x):\n"
                    "    return unroll(mat, x)\n"),
    })
    assert findings_for_dir(root) == []


def findings_for_dir(root, options=None):
    return analyze_paths([str(root)], options=options).findings


# ---------------------------------------------------------------------------
# CSA6xx sharding / collective consistency
# ---------------------------------------------------------------------------

def test_sharding_flags_unbound_collective_axis(tmp_path):
    src = (
        "import jax\n"
        "from jax.sharding import Mesh\n"
        "mesh = Mesh(None, axis_names=('v',))\n"
        "def f(x):\n"
        "    return jax.lax.psum(x, 'w')\n"    # typo: no mesh binds 'w'
    )
    assert rule_ids(findings_for(tmp_path, src)) == ["CSA601"]


def test_sharding_negative_bound_axes_and_suppression(tmp_path):
    src = (
        "import jax\n"
        "from jax.sharding import Mesh, NamedSharding, PartitionSpec as P\n"
        "mesh = Mesh(None, axis_names=('host', 'v'))\n"
        "spec = P(('host', 'v'))\n"
        "def f(x):\n"
        "    return jax.lax.psum(x, ('host', 'v'))\n"
        "def g(x):\n"
        "    return jax.lax.psum(x, 'q')  # csa: ignore[CSA601] -- doc\n"
    )
    path = tmp_path / "s.py"
    path.write_text(src)
    report = analyze_paths([str(path)])
    assert report.findings == []
    assert [f.rule for f in report.suppressed] == ["CSA601"]


def test_sharding_flags_unknown_partition_spec_axis(tmp_path):
    src = (
        "from jax.sharding import Mesh, PartitionSpec as P\n"
        "mesh = Mesh(None, axis_names=('v',))\n"
        "spec = P('validators')\n"             # not a mesh axis
    )
    assert rule_ids(findings_for(tmp_path, src)) == ["CSA602"]


def test_sharding_negative_partition_spec_none_entries(tmp_path):
    src = (
        "from jax.sharding import Mesh, PartitionSpec as P\n"
        "mesh = Mesh(None, axis_names=('v',))\n"
        "spec = P(None, 'v')\n"
    )
    assert findings_for(tmp_path, src) == []


def test_sharding_flags_bare_constraint_outside_mesh(tmp_path):
    src = (
        "import jax\n"
        "from jax.sharding import Mesh, PartitionSpec as P\n"
        "mesh = Mesh(None, axis_names=('v',))\n"
        "def f(x):\n"
        "    return jax.lax.with_sharding_constraint(x, P('v'))\n"
    )
    assert rule_ids(findings_for(tmp_path, src)) == ["CSA603"]


def test_sharding_negative_constraint_under_mesh_scope(tmp_path):
    src = (
        "import jax\n"
        "from jax.sharding import Mesh, PartitionSpec as P\n"
        "mesh = Mesh(None, axis_names=('v',))\n"
        "def f(x):\n"
        "    with mesh:\n"
        "        return jax.lax.with_sharding_constraint(x, P('v'))\n"
    )
    assert findings_for(tmp_path, src) == []


def test_sharding_flags_producer_consumer_spec_mismatch(tmp_path):
    src = (
        "import jax\n"
        "from jax.sharding import Mesh, NamedSharding, PartitionSpec as P\n"
        "mesh = Mesh(None, axis_names=('v',))\n"
        "def f(x):\n"
        "    y = jax.device_put(x, NamedSharding(mesh, P('v')))\n"
        "    z = jax.device_put(y, NamedSharding(mesh, P(None, 'v')))\n"
        "    return z\n"
    )
    assert rule_ids(findings_for(tmp_path, src)) == ["CSA604"]


def test_sharding_negative_named_spec_matches_inline(tmp_path):
    # a spec bound to a named constant is the SAME spec, not a reshard
    src = (
        "import jax\n"
        "from jax.sharding import Mesh, NamedSharding, PartitionSpec as P\n"
        "mesh = Mesh(None, axis_names=('v',))\n"
        "SPEC = NamedSharding(mesh, P('v'))\n"
        "def f(x):\n"
        "    y = jax.device_put(x, NamedSharding(mesh, P('v')))\n"
        "    z = jax.device_put(y, SPEC)\n"
        "    return z\n"
    )
    assert findings_for(tmp_path, src) == []


def test_callgraph_jitted_name_reexport_chain(tmp_path):
    # a -> re-exported by b -> called in c: CSA501 must fire regardless
    # of module iteration order (names chosen to sort c before b)
    root = _write_pkg(tmp_path, {
        "z_src.py": ("import jax\ndef _f(x):\n    return x\n"
                     "f_jit = jax.jit(_f)\n"),
        "m_mid.py": "from .z_src import f_jit\n",
        "a_use.py": ("from .m_mid import f_jit\n"
                     "def run():\n    return f_jit(3)\n"),
    })
    found = findings_for_dir(root)
    assert rule_ids(found) == ["CSA501"]
    assert found[0].path.endswith("a_use.py")


def test_sharding_negative_consistent_producer_consumer(tmp_path):
    src = (
        "import jax\n"
        "from jax.sharding import Mesh, NamedSharding, PartitionSpec as P\n"
        "mesh = Mesh(None, axis_names=('v',))\n"
        "def f(x):\n"
        "    y = jax.device_put(x, NamedSharding(mesh, P('v')))\n"
        "    z = jax.device_put(y, NamedSharding(mesh, P('v')))\n"
        "    return z\n"
    )
    assert findings_for(tmp_path, src) == []


def test_sharding_flags_chained_jit_sharding_mismatch(tmp_path):
    """CSA605: a jitted producer's out_shardings feeding a jitted consumer
    whose in_shardings disagree at that argument position — the serving-
    loop contract (SNIPPETS.md [1]) checked statically."""
    src = (
        "import jax\n"
        "from jax.sharding import Mesh, NamedSharding, PartitionSpec as P\n"
        "mesh = Mesh(None, axis_names=('v',))\n"
        "def serve(x):\n"
        "    step = jax.jit(lambda a: a,\n"
        "                   in_shardings=NamedSharding(mesh, P('v')),\n"
        "                   out_shardings=NamedSharding(mesh, P('v')))\n"
        "    gather = jax.jit(lambda a: a,\n"
        "                     in_shardings=NamedSharding(mesh, P()),\n"
        "                     out_shardings=NamedSharding(mesh, P()))\n"
        "    y = step(x)\n"
        "    return gather(y)\n"        # P('v') output into P() input
    )
    assert rule_ids(findings_for(tmp_path, src)) == ["CSA605"]


def test_sharding_negative_chained_jit_matched_shardings(tmp_path):
    """Matched out/in shardings — including specs named by a constant and
    tuple outputs unpacked into the next call — produce no finding."""
    src = (
        "import jax\n"
        "from jax.sharding import Mesh, NamedSharding, PartitionSpec as P\n"
        "mesh = Mesh(None, axis_names=('v',))\n"
        "SH = NamedSharding(mesh, P('v'))\n"
        "def serve(x, s):\n"
        "    step = jax.jit(lambda a, b: (a, b),\n"
        "                   in_shardings=(SH, NamedSharding(mesh, P())),\n"
        "                   out_shardings=(NamedSharding(mesh, P('v')),\n"
        "                                  NamedSharding(mesh, P())))\n"
        "    cols, scal = step(x, s)\n"
        "    cols, scal = step(cols, scal)\n"   # chained, matched per-arg
        "    return cols\n"
    )
    assert findings_for(tmp_path, src) == []


def test_sharding_negative_chained_jit_rebound_value(tmp_path):
    """An explicit re-layout (or any rebinding) between producer and
    consumer invalidates the recorded out-sharding — deliberate gathers
    must not be flagged as implicit reshards."""
    src = (
        "import jax\n"
        "from jax.sharding import Mesh, NamedSharding, PartitionSpec as P\n"
        "mesh = Mesh(None, axis_names=('v',))\n"
        "def serve(x):\n"
        "    step = jax.jit(lambda a: a,\n"
        "                   in_shardings=NamedSharding(mesh, P('v')),\n"
        "                   out_shardings=NamedSharding(mesh, P('v')))\n"
        "    gather = jax.jit(lambda a: a,\n"
        "                     in_shardings=NamedSharding(mesh, P()))\n"
        "    y = step(x)\n"
        "    y = jax.device_put(y, NamedSharding(mesh, P()))\n"
        "    return gather(y)\n"       # explicit re-layout: no finding
    )
    assert findings_for(tmp_path, src) == []
    # non-Assign rebindings (AugAssign here) invalidate the same way
    src_aug = src.replace(
        "    y = jax.device_put(y, NamedSharding(mesh, P()))\n",
        "    y += 1\n")
    assert findings_for(tmp_path, src_aug) == []


def test_sharding_chained_jit_mismatch_suppressible(tmp_path):
    src = (
        "import jax\n"
        "from jax.sharding import Mesh, NamedSharding, PartitionSpec as P\n"
        "mesh = Mesh(None, axis_names=('v',))\n"
        "def serve(x):\n"
        "    step = jax.jit(lambda a: a,\n"
        "                   in_shardings=NamedSharding(mesh, P('v')),\n"
        "                   out_shardings=NamedSharding(mesh, P('v')))\n"
        "    gather = jax.jit(lambda a: a,\n"
        "                     in_shardings=NamedSharding(mesh, P()))\n"
        "    y = step(x)\n"
        "    return gather(y)  # csa: ignore[CSA605] -- one-shot download\n"
    )
    path = tmp_path / "s.py"
    path.write_text(src)
    report = analyze_paths([str(path)])
    assert report.findings == []
    assert [f.rule for f in report.suppressed] == ["CSA605"]


# ---------------------------------------------------------------------------
# CSA7xx pallas kernel constraints
# ---------------------------------------------------------------------------

_PALLAS_HEADER = (
    "import jax\n"
    "from jax.experimental import pallas as pl\n"
    "def k(x_ref, o_ref):\n"
    "    o_ref[0, :] = x_ref[0, :]\n"
)


def test_pallas_flags_index_map_arity_and_rank(tmp_path):
    src = _PALLAS_HEADER + (
        "def run(x):\n"
        "    return pl.pallas_call(k, grid=(4,),\n"
        "        in_specs=[pl.BlockSpec((8, 128), lambda i, j: (0, i))],\n"
        "        out_specs=pl.BlockSpec((8, 128), lambda i: (i,)),\n"
        "        interpret=True)(x)\n"
    )
    # in spec: 2 lambda args vs rank-1 grid; out spec: 1 index for a
    # rank-2 block
    assert rule_ids(findings_for(tmp_path, src)) == ["CSA701", "CSA701"]


def test_pallas_flags_traced_grid(tmp_path):
    src = _PALLAS_HEADER + (
        "@jax.jit\n"
        "def run(x, n):\n"
        "    return pl.pallas_call(k, grid=(n,),\n"
        "        in_specs=[pl.BlockSpec((8, 128), lambda i: (0, i))],\n"
        "        out_specs=pl.BlockSpec((8, 128), lambda i: (0, i)),\n"
        "        interpret=True)(x)\n"
    )
    assert rule_ids(findings_for(tmp_path, src)) == ["CSA702"]


def test_pallas_flags_missing_interpret_escape_hatch(tmp_path):
    src = _PALLAS_HEADER + (
        "def run(x):\n"
        "    return pl.pallas_call(k, grid=(4,),\n"
        "        in_specs=[pl.BlockSpec((8, 128), lambda i: (0, i))],\n"
        "        out_specs=pl.BlockSpec((8, 128), lambda i: (0, i)))(x)\n"
    )
    assert rule_ids(findings_for(tmp_path, src)) == ["CSA703"]


def test_pallas_flags_out_of_block_ref_access(tmp_path):
    src = (
        "import jax\n"
        "from jax.experimental import pallas as pl\n"
        "def k(x_ref, o_ref):\n"
        "    o_ref[9, :] = x_ref[0, :, 0]\n"   # 9 >= 8; rank 3 > rank 2
        "def run(x):\n"
        "    return pl.pallas_call(k, grid=(4,),\n"
        "        in_specs=[pl.BlockSpec((8, 128), lambda i: (0, i))],\n"
        "        out_specs=pl.BlockSpec((8, 128), lambda i: (0, i)),\n"
        "        interpret=True)(x)\n"
    )
    assert rule_ids(findings_for(tmp_path, src)) == ["CSA704", "CSA704"]


def test_pallas_negative_consistent_call(tmp_path):
    # the sha256_pallas shape: named specs, static shapes from .shape,
    # loop-variable indices, paired compiled/interpret call sites
    src = (
        "import jax\n"
        "from jax.experimental import pallas as pl\n"
        "def k(x_ref, o_ref):\n"
        "    for i in range(8):\n"
        "        o_ref[i, :] = x_ref[i, :]\n"
        "def run(x, interpret=False):\n"
        "    n = x.shape[1]\n"
        "    spec = pl.BlockSpec((8, 128), lambda i: (0, i))\n"
        "    grid = (n // 128,)\n"
        "    return pl.pallas_call(k, grid=grid,\n"
        "        in_specs=[spec], out_specs=spec,\n"
        "        interpret=interpret)(x)\n"
    )
    assert findings_for(tmp_path, src) == []
    report = analyze_paths(
        [str(REPO / "consensus_specs_tpu" / "ops" / "sha256_pallas.py")])
    assert report.findings == []


def test_pallas_suppression(tmp_path):
    src = _PALLAS_HEADER + (
        "def run(x):\n"
        "    # csa: ignore[CSA703] -- TPU-only by design\n"
        "    return pl.pallas_call(k, grid=(4,),\n"
        "        in_specs=[pl.BlockSpec((8, 128), lambda i: (0, i))],\n"
        "        out_specs=pl.BlockSpec((8, 128), lambda i: (0, i)))(x)\n"
    )
    path = tmp_path / "s.py"
    path.write_text(src)
    report = analyze_paths([str(path)])
    assert report.findings == []
    assert [f.rule for f in report.suppressed] == ["CSA703"]


# ---------------------------------------------------------------------------
# CSA901 wide-column accumulation (the double-width lazy-Montgomery budget)
# ---------------------------------------------------------------------------

def test_wide_accumulation_flags_three_term_sum(tmp_path):
    src = (
        "from consensus_specs_tpu.ops import fq as F\n"
        "def f(a, b, c):\n"
        "    t0 = F.fq_mul_wide(a, b)\n"
        "    t1 = F.fq_mul_wide(a, c)\n"
        "    t2 = F.fq_mul_wide(b, c)\n"
        "    return t0 + t1 - t2\n"
    )
    found = findings_for(tmp_path, src)
    assert rule_ids(found) == ["CSA901"]
    assert found[0].severity == "notice"


def test_wide_accumulation_flags_augassign_loop(tmp_path):
    # taint accumulates through rebinding and +=
    src = (
        "from consensus_specs_tpu.ops import fq as F\n"
        "def f(a, bs):\n"
        "    acc = F.fq_mul_wide(a, bs[0])\n"
        "    acc += F.fq_mul_wide(a, bs[1])\n"
        "    acc += F.fq_mul_wide(a, bs[2])\n"
        "    return acc\n"
    )
    assert rule_ids(findings_for(tmp_path, src)) == ["CSA901"]


def test_wide_accumulation_flags_matrix_over_raw_columns(tmp_path):
    src = (
        "from consensus_specs_tpu.ops import fq as F\n"
        "from consensus_specs_tpu.ops.fq_tower import _apply_int_matrix\n"
        "def f(gamma, a, b):\n"
        "    P = F.fq_mul_wide(a, b)\n"
        "    return _apply_int_matrix(gamma, P)\n"
    )
    assert rule_ids(findings_for(tmp_path, src)) == ["CSA901"]


def test_wide_accumulation_negative_normed_and_shallow(tmp_path):
    # the shipped pipeline shape: fq_wide_norm clears the taint, and a
    # 2-term raw sum is inside the int64 headroom
    src = (
        "from consensus_specs_tpu.ops import fq as F\n"
        "from consensus_specs_tpu.ops.fq_tower import _apply_int_matrix\n"
        "def f(gamma, a, b, c):\n"
        "    P = F.fq_wide_norm(F.fq_mul_wide(a, b))\n"
        "    t = F.fq_mul_wide(a, c)\n"
        "    u = F.fq_mul_wide(b, c)\n"
        "    shallow = t - u\n"
        "    deep = P + P + P + P\n"
        "    return _apply_int_matrix(gamma, P) + shallow + deep\n"
    )
    assert findings_for(tmp_path, src) == []


def test_wide_accumulation_suppression(tmp_path):
    src = (
        "from consensus_specs_tpu.ops import fq as F\n"
        "def f(a, b, c):\n"
        "    # csa: ignore[CSA901] -- operands are half-width here\n"
        "    return F.fq_mul_wide(a, b) + F.fq_mul_wide(a, c) + "
        "F.fq_mul_wide(b, c)\n"
    )
    path = tmp_path / "s.py"
    path.write_text(src)
    report = analyze_paths([str(path)])
    assert report.findings == []
    assert [f.rule for f in report.suppressed] == ["CSA901"]


# ---------------------------------------------------------------------------
# CSA1001 honest timing (perf_counter around async dispatch with no fence)
# ---------------------------------------------------------------------------

_JIT_PREAMBLE = (
    "import jax, time\n"
    "import numpy as np\n"
    "def f(x):\n"
    "    return x\n"
    "f_jit = jax.jit(f)\n"
)


def test_honest_timing_flags_unfenced_delta(tmp_path):
    src = _JIT_PREAMBLE + (
        "def bench(x):\n"
        "    t0 = time.perf_counter()\n"
        "    y = f_jit(x)\n"
        "    dt = time.perf_counter() - t0\n"
        "    return y, dt\n"
    )
    found = findings_for(tmp_path, src)
    assert rule_ids(found) == ["CSA1001"]
    assert found[0].context == "bench"


def test_honest_timing_flags_chained_bucket_style(tmp_path):
    # the t0/t1/t2 style epoch_soa used to hand-roll: the next
    # perf_counter assignment closes the open region
    src = _JIT_PREAMBLE + (
        "def bench(x):\n"
        "    t0 = time.perf_counter()\n"
        "    y = f_jit(x)\n"
        "    t1 = time.perf_counter()\n"
        "    return y, t1 - t0\n"
    )
    assert rule_ids(findings_for(tmp_path, src)) == ["CSA1001"]


def test_honest_timing_negative_fenced(tmp_path):
    # every repo fence idiom clears the region, including inside the
    # timed loop body
    for fence in ("jax.block_until_ready(y)",
                  "np.asarray(y.ravel()[0:1])",
                  "y = y.tolist()"):
        src = _JIT_PREAMBLE + (
            "def bench(x):\n"
            "    t0 = time.perf_counter()\n"
            "    y = f_jit(x)\n"
            f"    {fence}\n"
            "    dt = time.perf_counter() - t0\n"
            "    return dt\n"
        )
        assert findings_for(tmp_path, src) == [], fence
    src = _JIT_PREAMBLE + (
        "def _sync(o):\n"
        "    return np.asarray(o)\n"
        "def bench(x):\n"
        "    t0 = time.perf_counter()\n"
        "    for _ in range(3):\n"
        "        _sync(f_jit(x))\n"
        "    return time.perf_counter() - t0\n"
    )
    assert findings_for(tmp_path, src) == []


def test_honest_timing_negative_no_dispatch(tmp_path):
    # a plain host computation between the reads is not a finding
    src = _JIT_PREAMBLE + (
        "def bench(x):\n"
        "    t0 = time.perf_counter()\n"
        "    y = x + 1\n"
        "    return time.perf_counter() - t0\n"
    )
    assert findings_for(tmp_path, src) == []


def test_honest_timing_flags_attribute_call_dispatch(tmp_path):
    """The documented CSA1001 gap, closed: an unfenced delta around a
    module-ATTRIBUTE dispatch (`kern.f_jit(x)`) of a jitted name
    resolved through the call-graph IR."""
    root = _write_pkg(tmp_path, {
        "kern.py": ("import jax\ndef _f(x):\n    return x\n"
                    "f_jit = jax.jit(_f)\n"),
        "drv.py": ("import time\nfrom . import kern\n"
                   "def bench(x):\n"
                   "    t0 = time.perf_counter()\n"
                   "    y = kern.f_jit(x)\n"
                   "    dt = time.perf_counter() - t0\n"
                   "    return y, dt\n"),
    })
    found = [f for f in findings_for_dir(root) if f.rule == "CSA1001"]
    assert len(found) == 1
    assert found[0].path.endswith("drv.py")
    assert found[0].context == "bench"


def test_honest_timing_attribute_call_negative_fenced_and_unjitted(
        tmp_path):
    # a fenced attribute dispatch is clean, and an attribute call whose
    # target module has no such jitted name never fires
    root = _write_pkg(tmp_path, {
        "kern.py": ("import jax\ndef _f(x):\n    return x\n"
                    "f_jit = jax.jit(_f)\n"
                    "def host_helper(x):\n    return x\n"),
        "drv.py": ("import time\nimport numpy as np\nfrom . import kern\n"
                   "def bench(x):\n"
                   "    t0 = time.perf_counter()\n"
                   "    y = kern.f_jit(x)\n"
                   "    np.asarray(y)\n"
                   "    dt = time.perf_counter() - t0\n"
                   "    t1 = time.perf_counter()\n"
                   "    z = kern.host_helper(x)\n"
                   "    return y, z, dt, time.perf_counter() - t1\n"),
    })
    assert [f for f in findings_for_dir(root) if f.rule == "CSA1001"] == []


def test_honest_timing_attribute_call_suppressible(tmp_path):
    root = _write_pkg(tmp_path, {
        "kern.py": ("import jax\ndef _f(x):\n    return x\n"
                    "f_jit = jax.jit(_f)\n"),
        "drv.py": ("import time\nfrom . import kern\n"
                   "def bench(x):\n"
                   "    t0 = time.perf_counter()\n"
                   "    y = kern.f_jit(x)\n"
                   "    # csa: ignore[CSA1001] -- launch-overhead probe\n"
                   "    dt = time.perf_counter() - t0\n"
                   "    return y, dt\n"),
    })
    report = analyze_paths([str(root)])
    assert [f for f in report.findings if f.rule == "CSA1001"] == []
    assert [f.rule for f in report.suppressed] == ["CSA1001"]


def test_honest_timing_suppression(tmp_path):
    src = _JIT_PREAMBLE + (
        "def bench(x):\n"
        "    t0 = time.perf_counter()\n"
        "    y = f_jit(x)\n"
        "    # csa: ignore[CSA1001] -- dispatch-only timing on purpose\n"
        "    dt = time.perf_counter() - t0\n"
        "    return y, dt\n"
    )
    path = tmp_path / "s.py"
    path.write_text(src)
    report = analyze_paths([str(path)])
    assert report.findings == []
    assert [f.rule for f in report.suppressed] == ["CSA1001"]


# ---------------------------------------------------------------------------
# CSA8xx spec drift (differential vs a reference tree)
# ---------------------------------------------------------------------------

def _mini_reference(tmp_path):
    ref = tmp_path / "reference"
    presets = ref / "configs" / "constant_presets"
    presets.mkdir(parents=True)
    (presets / "minimal.yaml").write_text(
        "# comment\n"
        "SHUFFLE_ROUND_COUNT: 10\n"
        "MAX_EFFECTIVE_BALANCE: 32000000000\n"
        "NEW_CONST: 7\n"
        "GENESIS_FORK_VERSION: '0x00000000'\n"
    )
    pyspec = ref / "test_libs" / "pyspec" / "eth2spec"
    pyspec.mkdir(parents=True)
    (pyspec / "spec.py").write_text(
        "def get_current_epoch(state):\n    return state.slot\n"
        "def integer_squareroot(n):\n    return n\n"
        "def slot_to_epoch(slot):\n    return slot\n"
        "def _private_helper(x):\n    return x\n"
    )
    return ref


def _mini_port(tmp_path, helpers_src):
    port = tmp_path / "port"
    tree = port / "models" / "phase0"
    tree.mkdir(parents=True)
    for d in (port, port / "models", tree):
        (d / "__init__.py").write_text("")
    (tree / "spec.py").write_text("")
    (tree / "helpers.py").write_text(helpers_src)
    cfg = tmp_path / "portcfg"
    cfg.mkdir()
    (cfg / "minimal.yaml").write_text(
        "SHUFFLE_ROUND_COUNT: 90\n"                # drifted value
        "MAX_EFFECTIVE_BALANCE: 32000000000\n"
        "GENESIS_FORK_VERSION: '0x00000000'\n"     # quoting-insensitive
    )
    return port, cfg


def test_spec_drift_reports_constant_function_and_signature_drift(tmp_path):
    ref = _mini_reference(tmp_path)
    port, cfg = _mini_port(tmp_path, (
        "def get_current_epoch(spec, state):\n    return state.slot\n"
        "def integer_squareroot(spec, value):\n    return value\n"
    ))
    report = analyze_paths([str(port)], options={
        "reference_root": str(ref), "drift_port_configs": str(cfg)})
    got = rule_ids(report.findings)
    # SHUFFLE_ROUND_COUNT drifted, NEW_CONST missing, slot_to_epoch
    # missing, integer_squareroot renamed its parameter
    assert got == ["CSA801", "CSA802", "CSA803", "CSA804"]
    by_rule = {f.rule: f for f in report.findings}
    assert "SHUFFLE_ROUND_COUNT" in by_rule["CSA801"].message
    assert "NEW_CONST" in by_rule["CSA802"].message
    assert "slot_to_epoch" in by_rule["CSA803"].message
    assert "integer_squareroot" in by_rule["CSA804"].message


def test_spec_drift_negative_conforming_port(tmp_path):
    ref = _mini_reference(tmp_path)
    port, cfg = _mini_port(tmp_path, (
        "def get_current_epoch(spec, state):\n    return state.slot\n"
        "def integer_squareroot(spec, n):\n    return n\n"
        "def slot_to_epoch(spec, slot):\n    return slot\n"
        "def extra_port_only_fn(spec, x):\n    return x\n"
    ))
    (cfg / "minimal.yaml").write_text(
        "SHUFFLE_ROUND_COUNT: 10\n"
        "MAX_EFFECTIVE_BALANCE: 32000000000\n"
        "NEW_CONST: 7\n"
        "GENESIS_FORK_VERSION: 0x00000000\n"
    )
    report = analyze_paths([str(port)], options={
        "reference_root": str(ref), "drift_port_configs": str(cfg)})
    assert report.findings == []


def test_spec_drift_skips_with_notice_when_reference_absent(tmp_path):
    port, cfg = _mini_port(tmp_path, "def f(spec, x):\n    return x\n")
    missing = tmp_path / "no-such-reference"
    report = analyze_paths([str(port)], options={
        "reference_root": str(missing), "drift_port_configs": str(cfg)})
    assert report.findings == []
    assert any("spec-drift" in n and "skipped" in n for n in report.notices)


def test_spec_drift_baseline_entries_not_stale_when_pass_skipped(tmp_path):
    """A deliberate-divergence CSA8xx baseline entry recorded where the
    reference exists must not read as stale on machines without it —
    the skipped pass makes the entry unverifiable, not fixed."""
    ref = _mini_reference(tmp_path)
    port, cfg = _mini_port(tmp_path, (
        "def get_current_epoch(spec, state):\n    return state.slot\n"
        "def integer_squareroot(spec, n):\n    return n\n"
        "def slot_to_epoch(spec, slot):\n    return slot\n"))
    opts = {"reference_root": str(ref), "drift_port_configs": str(cfg)}
    with_ref = analyze_paths([str(port)], options=opts)
    assert "CSA801" in rule_ids(with_ref.findings)
    bl_path = tmp_path / "baseline.json"
    write_baseline(str(bl_path), with_ref.findings)
    baseline = load_baseline(str(bl_path))
    # with the reference: baselined, nothing stale
    again = analyze_paths([str(port)], baseline, options=opts)
    assert again.findings == [] and again.stale_baseline == []
    # without it: the pass skips, the entries stay exempt (CI machines)
    without = analyze_paths([str(port)], baseline, options={
        "reference_root": str(tmp_path / "gone"),
        "drift_port_configs": str(cfg)})
    assert without.findings == [] and without.stale_baseline == []


def test_callgraph_ambiguous_module_names_both_scanned(tmp_path):
    """Two targets mapping to one dotted name must both be analyzed,
    in either order (a silent drop was order-dependent)."""
    a = tmp_path / "a"
    b = tmp_path / "b"
    a.mkdir()
    b.mkdir()
    (a / "util.py").write_text(
        "from jax.sharding import Mesh\n"
        "mesh = Mesh(None, axis_names=('v',))\n")
    (b / "util.py").write_text(
        "import jax\ndef f(x):\n    return jax.lax.psum(x, 'v')\n")
    for targets in ([str(a / "util.py"), str(b / "util.py")],
                    [str(b / "util.py"), str(a / "util.py")]):
        report = analyze_paths(targets)
        assert report.findings == []       # a's mesh axes always visible
        assert any("ambiguous" in n for n in report.notices)


def test_pallas_blockspec_names_resolve_per_function(tmp_path):
    # two functions reusing the name `spec` for different-rank BlockSpecs
    # must each be checked against their OWN spec
    src = (
        "from jax.experimental import pallas as pl\n"
        "def k2(x_ref, o_ref):\n"
        "    o_ref[0, :] = x_ref[0, :]\n"
        "def k1(x_ref, o_ref):\n"
        "    o_ref[0] = x_ref[0]\n"
        "def f(x):\n"
        "    spec = pl.BlockSpec((8, 128), lambda i: (0, i))\n"
        "    return pl.pallas_call(k2, grid=(4,), in_specs=[spec],\n"
        "        out_specs=spec, interpret=True)(x)\n"
        "def g(x):\n"
        "    spec = pl.BlockSpec((128,), lambda i: (i,))\n"
        "    return pl.pallas_call(k1, grid=(4,), in_specs=[spec],\n"
        "        out_specs=spec, interpret=True)(x)\n"
    )
    assert findings_for(tmp_path, src) == []


# ---------------------------------------------------------------------------
# framework: baseline ratchet + CLI + repo green
# ---------------------------------------------------------------------------

def test_baseline_roundtrip_and_stale_detection(tmp_path):
    path = tmp_path / "s.py"
    path.write_text(PRE_FIX_RESIDENT_SNIPPET)
    report = analyze_paths([str(path)])
    assert len(report.findings) == 2

    bl_path = tmp_path / "baseline.json"
    write_baseline(str(bl_path), report.findings)
    baseline = load_baseline(str(bl_path))
    ratcheted = analyze_paths([str(path)], baseline)
    assert ratcheted.findings == []
    assert len(ratcheted.baselined) == 2
    assert ratcheted.stale_baseline == []

    # fix one of the two: its baseline entry goes stale, run stays green
    path.write_text(PRE_FIX_RESIDENT_SNIPPET.replace(
        "return int(mirrors['effective_balance'][index])",
        "return int(state.validator_registry[index].effective_balance)"))
    after_fix = analyze_paths([str(path)], baseline)
    assert after_fix.findings == []
    assert len(after_fix.stale_baseline) == 1


def test_update_baseline_preserves_live_entries_and_reasons(tmp_path):
    """Refreshing the baseline must keep still-live entries (with their
    hand-written reasons), not reset the file to just-new findings."""
    path = tmp_path / "s.py"
    path.write_text(PRE_FIX_RESIDENT_SNIPPET)
    first = analyze_paths([str(path)])
    live_fp = first.findings[0].fingerprint()

    bl_path = tmp_path / "baseline.json"
    write_baseline(str(bl_path), [first.findings[0]])
    # hand-edit the reason, as the README instructs
    data = json.loads(bl_path.read_text())
    data["entries"][0]["reason"] = "deliberate: documented at the site"
    bl_path.write_text(json.dumps(data))

    baseline = load_baseline(str(bl_path))
    report = analyze_paths([str(path)], baseline)
    assert len(report.findings) == 1 and len(report.baselined) == 1
    # the --update-baseline merge: actionable + still-baselined, reasons
    # carried over for entries that were already in the file
    write_baseline(str(bl_path), report.findings + report.baselined,
                   prior=baseline)
    merged = json.loads(bl_path.read_text())["entries"]
    assert len(merged) == 2
    by_fp = {e["fingerprint"]: e["reason"] for e in merged}
    assert by_fp[live_fp] == "deliberate: documented at the site"
    refreshed = analyze_paths([str(path)], load_baseline(str(bl_path)))
    assert refreshed.findings == [] and refreshed.stale_baseline == []


def _run_cli(args, cwd=REPO):
    return subprocess.run(
        [sys.executable, "-m", "tools.analysis", *args],
        cwd=cwd, capture_output=True, text=True)


def test_cli_exit_codes_and_json(tmp_path):
    dirty = tmp_path / "dirty.py"
    dirty.write_text(PRE_FIX_RESIDENT_SNIPPET)
    clean = tmp_path / "clean.py"
    clean.write_text("def f(state):\n    return state.slot\n")
    out_json = tmp_path / "analysis.json"

    proc = _run_cli([str(dirty), "--json", str(out_json)])
    assert proc.returncode == 1
    assert "CSA401" in proc.stdout
    data = json.loads(out_json.read_text())
    assert [f["rule"] for f in data["findings"]] == ["CSA401", "CSA401"]

    assert _run_cli([str(clean)]).returncode == 0
    assert _run_cli(["--list-rules"]).returncode == 0


@pytest.mark.parametrize("rule_class,snippet", [
    ("CSA101", "import jax\n@jax.jit\ndef f(x):\n    if x > 0:\n"
               "        return x\n    return -x\n"),
    ("CSA201", "import jax\nimport jax.numpy as jnp\n@jax.jit\n"
               "def f(x):\n    return x + jnp.zeros(3)\n"),
    ("CSA301", "import jax, time\n@jax.jit\ndef f(x):\n"
               "    return x + time.time()\n"),
    ("CSA401", "def f(state):\n    return 1\n"),
    ("CSA501", "import jax\ndef f(x):\n    return x\n"
               "f_jit = jax.jit(f)\ny = f_jit(3)\n"),
    ("CSA601", "import jax\ndef f(x):\n"
               "    return jax.lax.psum(x, 'ghost')\n"),
    ("CSA701", "from jax.experimental import pallas as pl\n"
               "def k(x_ref):\n    x_ref[0] = 0\n"
               "def run(x):\n"
               "    return pl.pallas_call(k, grid=(2, 2),\n"
               "        out_specs=pl.BlockSpec((8,), lambda i: (i,)),\n"
               "        interpret=True)(x)\n"),
    ("CSA901", "def f(a, b, c):\n"
               "    return (fq_mul_wide(a, b) + fq_mul_wide(a, c)\n"
               "            + fq_mul_wide(b, c))\n"),
    ("CSA1001", "import jax, time\ndef f(x):\n    return x\n"
                "f_jit = jax.jit(f)\n"
                "def bench(x):\n"
                "    t0 = time.perf_counter()\n"
                "    y = f_jit(x)\n"
                "    return time.perf_counter() - t0\n"),
])
def test_cli_nonzero_per_rule_class(tmp_path, rule_class, snippet):
    """Acceptance: injected fixtures for each per-module rule class exit
    non-zero through the real CLI (CSA8xx is differential — covered by
    the spec-drift fixtures above)."""
    path = tmp_path / "inject.py"
    path.write_text(snippet)
    proc = _run_cli([str(path)])
    assert proc.returncode == 1
    assert rule_class in proc.stdout


def test_repo_is_analysis_clean():
    """The `make analyze` guarantee, asserted in-process: the shipped tree
    has no actionable findings over the committed baseline, the baseline
    carries no stale entries (any rule family, including CSA6xx-8xx —
    the ratchet only shrinks), and every baseline entry names a rule the
    analyzer still registers."""
    baseline = load_baseline(str(REPO / "tools" / "analysis" / "baseline.json"))
    report = analyze_paths(
        [str(REPO / "consensus_specs_tpu"), str(REPO / "chip_smoke.py"),
         str(REPO / "__graft_entry__.py")], baseline)
    assert report.findings == []
    assert report.stale_baseline == []
    for fingerprint in baseline:
        rule = fingerprint.split("::")[1]
        assert rule in RULES, f"baseline entry for unknown rule {rule}"
    # the reference tree is not shipped with the repo: the differential
    # pass must announce it skipped rather than silently pass
    if not (Path("/root/reference").is_dir()
            or "CSTPU_REFERENCE_ROOT" in __import__("os").environ):
        assert any("spec-drift" in n for n in report.notices)


def test_rule_catalog_documented():
    """Every registered rule appears in tools/analysis/README.md."""
    readme = (REPO / "tools" / "analysis" / "README.md").read_text()
    for rule_id in RULES:
        assert rule_id in readme, f"{rule_id} missing from README"
