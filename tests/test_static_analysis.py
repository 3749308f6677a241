"""tpu-lint (lightgbm_tpu.analysis): fixture battery per rule, repo
cleanliness, suppression/baseline workflow, reporters, and the JAX-free
import guarantee. Everything here is pure AST — the whole module must run in
well under 10 s (enforced below) so the lint stays a cheap tier-1 gate."""
import json
import os
import subprocess
import sys
import time

import pytest

from lightgbm_tpu.analysis import (all_rules, analyze_paths, analyze_source,
                                   event_schemas, load_baseline,
                                   registered_params, render_json)
from lightgbm_tpu.analysis.core import DEFAULT_BASELINE, REPO_ROOT

# ---------------------------------------------------------------------------
# fixture snippets: for each rule a (fires, suppressed, clean) trio


def names(findings):
    return [f.rule for f in findings]


# ---- host-sync-in-jit ----

HOST_SYNC_BAD = """
import jax
import numpy as np

@jax.jit
def f(x):
    return x.sum().item()
"""

HOST_SYNC_NP = """
import jax
import numpy as np

@jax.jit
def f(x):
    return np.asarray(x) + 1
"""

HOST_SYNC_STATIC_OK = """
import jax

@jax.jit
def f(x):
    return float(x.shape[0]) * x

def g(gp, x):
    return float(gp.lr) * x

g2 = jax.jit(g, static_argnames=("gp",))
"""

HOST_SYNC_SUPPRESSED = """
import jax

@jax.jit
def f(x):
    return x.sum().item()  # tpu-lint: disable=host-sync-in-jit
"""


def test_host_sync_fires():
    assert "host-sync-in-jit" in names(analyze_source(HOST_SYNC_BAD))
    assert "host-sync-in-jit" in names(analyze_source(HOST_SYNC_NP))


def test_host_sync_static_metadata_and_static_args_clean():
    assert "host-sync-in-jit" not in names(analyze_source(HOST_SYNC_STATIC_OK))


def test_host_sync_suppressed():
    assert "host-sync-in-jit" not in names(analyze_source(HOST_SYNC_SUPPRESSED))
    kept = analyze_source(HOST_SYNC_SUPPRESSED, keep_suppressed=True)
    assert "host-sync-in-jit" in names(kept)


# ---- retrace-hazard ----

RETRACE_JIT_IN_FN = """
import jax

def build(x):
    f = jax.jit(lambda a: a + 1)
    return f(x)
"""

RETRACE_UNDECLARED_STATIC = """
import jax

@jax.jit(static_argnames=("misspelled",))
def f(x, mode):
    return x
"""

RETRACE_UNHASHABLE_DEFAULT = """
import jax
from functools import partial

@partial(jax.jit, static_argnames=("opts",))
def f(x, opts=[1, 2]):
    return x
"""

RETRACE_ARGNUMS_OOR = """
import jax
from functools import partial

@partial(jax.jit, static_argnums=(5,))
def f(x, pack_k):
    return x * pack_k
"""

RETRACE_ARGNUMS_OOR_SUPPRESSED = """
import jax
from functools import partial

@partial(jax.jit, static_argnums=(5,))   # tpu-lint: disable=retrace-hazard
def f(x, pack_k):
    return x * pack_k
"""

RETRACE_ARGNUMS_CLEAN = """
import jax
from functools import partial

@partial(jax.jit, static_argnums=(1,))
def f(x, pack_k=0):
    return x * pack_k
"""

RETRACE_ARGNUMS_UNHASHABLE = """
import jax
from functools import partial

@partial(jax.jit, static_argnums=(1,))
def f(x, widths=[32, 128]):
    return x
"""

RETRACE_TRACED_BRANCH = """
import jax
import jax.numpy as jnp

@jax.jit
def f(x):
    if jnp.sum(x) > 0:
        return x
    return -x
"""

RETRACE_CLEAN = """
import jax
from functools import partial

@partial(jax.jit, static_argnames=("k",))
def f(x, k=3):
    # shape branching is trace-time static: fine
    if x.shape[0] > 2:
        return x * k
    return x

g = jax.jit(lambda a: a + 1)   # module level: built once
"""


def test_retrace_fires_on_jit_in_function():
    assert "retrace-hazard" in names(analyze_source(RETRACE_JIT_IN_FN))


def test_retrace_fires_on_undeclared_static():
    fs = analyze_source(RETRACE_UNDECLARED_STATIC)
    assert any(f.rule == "retrace-hazard" and "misspelled" in f.message
               for f in fs)


def test_retrace_fires_on_unhashable_static_default():
    fs = analyze_source(RETRACE_UNHASHABLE_DEFAULT)
    assert any(f.rule == "retrace-hazard" and "unhashable" in f.message
               for f in fs)


def test_retrace_fires_on_traced_branch():
    assert "retrace-hazard" in names(analyze_source(RETRACE_TRACED_BRANCH))


def test_retrace_fires_on_out_of_range_static_argnums():
    """static_argnums past the positional parameter list: the arg the
    index was meant to pin (a pack_k-style compile-time constant) stays
    traced, so every distinct value becomes an executable variant."""
    fs = analyze_source(RETRACE_ARGNUMS_OOR)
    assert any(f.rule == "retrace-hazard" and "out of range" in f.message
               for f in fs)


def test_retrace_argnums_in_range_clean():
    assert "retrace-hazard" not in names(analyze_source(RETRACE_ARGNUMS_CLEAN))


def test_retrace_argnums_oor_suppressed():
    assert "retrace-hazard" not in names(
        analyze_source(RETRACE_ARGNUMS_OOR_SUPPRESSED))
    kept = analyze_source(RETRACE_ARGNUMS_OOR_SUPPRESSED,
                          keep_suppressed=True)
    assert "retrace-hazard" in names(kept)


def test_retrace_fires_on_unhashable_default_at_argnums_position():
    """the static_argnums->name mapping feeds the unhashable-default check
    too (not just static_argnames)"""
    fs = analyze_source(RETRACE_ARGNUMS_UNHASHABLE)
    assert any(f.rule == "retrace-hazard" and "unhashable" in f.message
               for f in fs)


def test_retrace_clean_on_module_level_and_shape_branch():
    assert "retrace-hazard" not in names(analyze_source(RETRACE_CLEAN))


# ---- dtype-drift ----

DTYPE_BAD = """
import numpy as np
import jax.numpy as jnp

def f(x):
    acc = np.zeros(8, dtype=np.float64)
    return jnp.asarray(acc)
"""

DTYPE_IMPLICIT = """
import numpy as np
import jax.numpy as jnp

def f(n):
    acc = np.zeros(n)
    return jnp.asarray(acc)
"""

DTYPE_CLEAN = """
import numpy as np
import jax.numpy as jnp

def f(x):
    a = np.zeros(8, dtype=np.float64).astype(np.float32)   # transient f64
    b = np.ones(4, dtype=np.float32)
    return jnp.asarray(a) + jnp.asarray(b)

def pure_host(x):
    # no device API in this function: host f64 is fine
    return np.zeros(8, dtype=np.float64)
"""

DTYPE_SUPPRESSED = """
import numpy as np
import jax.numpy as jnp

def f(x):
    acc = np.zeros(8, dtype=np.float64)   # tpu-lint: disable=dtype-drift
    return jnp.asarray(acc.astype(np.float32))
"""


DTYPE_I64_BAD = """
import jax.numpy as jnp

def upload_words(words):
    # packed lattice words occupy bits up to 30: the silent narrow to
    # int32 under disabled x64 is exactly the hazard
    return jnp.asarray(words, dtype=jnp.int64)
"""

DTYPE_I64_CLEAN = """
import numpy as np
import jax.numpy as jnp

def f(words, n):
    # host-side numpy keeps its 64 bits: not a device request
    hi = np.asarray(words, dtype=np.int64)
    # transient wide int, immediately narrowed with an explicit dtype
    low = jnp.arange(n, dtype=jnp.int64).astype(jnp.int32)
    return jnp.asarray(hi >> 15, dtype=jnp.int32) + low
"""

DTYPE_I64_SUPPRESSED = """
import jax.numpy as jnp

def f(words):
    # words proven < 2**31 upstream by the guard-bit budget assert
    return jnp.asarray(words, dtype=jnp.int64)  # tpu-lint: disable=dtype-drift
"""


def test_dtype_drift_fires():
    assert "dtype-drift" in names(analyze_source(DTYPE_BAD))


def test_dtype_drift_fires_on_jnp_int64_request():
    fs = analyze_source(DTYPE_I64_BAD)
    assert any(f.rule == "dtype-drift" and "int64" in f.message for f in fs)


def test_dtype_drift_int64_clean_on_host_numpy_and_narrowed():
    assert "dtype-drift" not in names(analyze_source(DTYPE_I64_CLEAN))


def test_dtype_drift_int64_suppressed():
    assert "dtype-drift" not in names(analyze_source(DTYPE_I64_SUPPRESSED))
    kept = analyze_source(DTYPE_I64_SUPPRESSED, keep_suppressed=True)
    assert "dtype-drift" in names(kept)


def test_dtype_drift_flags_implicit_default():
    fs = analyze_source(DTYPE_IMPLICIT)
    assert any(f.rule == "dtype-drift" and f.severity == "warning"
               for f in fs)


def test_dtype_drift_clean():
    assert "dtype-drift" not in names(analyze_source(DTYPE_CLEAN))


def test_dtype_drift_suppressed():
    assert "dtype-drift" not in names(analyze_source(DTYPE_SUPPRESSED))


# ---- unregistered-param ----

def test_unregistered_param_fires():
    src = 'def f(params):\n    return params.get("no_such_knob_xyz", 3)\n'
    fs = analyze_source(src)
    assert any(f.rule == "unregistered-param" and "no_such_knob_xyz"
               in f.message for f in fs)


def test_registered_param_clean():
    known = registered_params()
    assert "num_leaves" in known and "learning_rate" in known
    src = ('def f(params):\n'
           '    return params["num_leaves"], params.get("learning_rate")\n')
    assert "unregistered-param" not in names(analyze_source(src))


def test_unregistered_param_on_config_attr():
    src = ('from .config import Config, params_to_config\n'
           'def f(params):\n'
           '    conf = params_to_config(params)\n'
           '    return conf.num_leaves + conf.definitely_not_a_param\n')
    fs = analyze_source(src)
    assert any(f.rule == "unregistered-param" and "definitely_not_a_param"
               in f.message for f in fs)
    assert not any("num_leaves" in f.message for f in fs)


# ---- non-atomic-artifact-write ----

def test_atomic_write_fires_and_suppresses():
    bad = 'def f(p, doc):\n    with open(p, "w") as fh:\n        fh.write(doc)\n'
    assert "non-atomic-artifact-write" in names(analyze_source(bad))
    ok = ('def f(p, doc):\n'
          '    with open(p, "w") as fh:'
          '   # tpu-lint: disable=non-atomic-artifact-write\n'
          '        fh.write(doc)\n')
    assert "non-atomic-artifact-write" not in names(analyze_source(ok))


def test_atomic_write_ignores_reads_and_atomic_io_module():
    read = 'def f(p):\n    with open(p) as fh:\n        return fh.read()\n'
    assert "non-atomic-artifact-write" not in names(analyze_source(read))
    bad = 'def f(p, d):\n    with open(p, "wb") as fh:\n        fh.write(d)\n'
    assert "non-atomic-artifact-write" not in names(
        analyze_source(bad, relpath="lightgbm_tpu/utils/atomic_io.py"))


# ---- unlocked-shared-state ----

SHARED_BAD = """
_CACHE = {}

def put(k, v):
    _CACHE[k] = v
"""

SHARED_GLOBAL_BAD = """
_active = None

def set_active(v):
    global _active
    _active = v
"""

SHARED_LOCKED = """
import threading

_CACHE = {}
_lock = threading.Lock()

def put(k, v):
    with _lock:
        _CACHE[k] = v

def set_active(v):
    global _active
    with _lock:
        _active = v
"""


def test_shared_state_fires_in_scope():
    rel = "lightgbm_tpu/obs/whatever.py"
    assert "unlocked-shared-state" in names(
        analyze_source(SHARED_BAD, relpath=rel))
    assert "unlocked-shared-state" in names(
        analyze_source(SHARED_GLOBAL_BAD, relpath=rel))


def test_shared_state_lock_and_out_of_scope_clean():
    rel = "lightgbm_tpu/obs/whatever.py"
    assert "unlocked-shared-state" not in names(
        analyze_source(SHARED_LOCKED, relpath=rel))
    # identical mutation outside serving/obs/ingest is the normal idiom
    assert "unlocked-shared-state" not in names(
        analyze_source(SHARED_BAD, relpath="lightgbm_tpu/engine.py"))


# ---- ingest-pipeline rule scopes (PR: pipelined cold-start) ----
# the chunked ingest module is multi-threaded, so both threading rules
# extend their scope to it; each gets its own fire / suppressed / clean trio

INGEST_HOT_LOOP_BAD = """
def _commit_loop():
    while True:
        acc = step()
        acc.block_until_ready()
"""

INGEST_HOT_LOOP_SUPPRESSED = """
def _h2d_loop():
    while True:
        dev = put()
        dev.block_until_ready()   # tpu-lint: disable=host-sync-in-jit
"""

INGEST_HOT_LOOP_CLEAN = """
def _h2d_loop():
    while True:
        dev = put()
        enqueue(dev)
"""

INGEST_REL = "lightgbm_tpu/ingest.py"


def test_ingest_hot_loops_fire():
    assert "host-sync-in-jit" in names(
        analyze_source(INGEST_HOT_LOOP_BAD, relpath=INGEST_REL))
    # the very same loop body outside the designated module is not audited
    assert "host-sync-in-jit" not in names(
        analyze_source(INGEST_HOT_LOOP_BAD, relpath="lightgbm_tpu/efb.py"))


def test_ingest_hot_loop_suppressed_and_clean():
    assert "host-sync-in-jit" not in names(
        analyze_source(INGEST_HOT_LOOP_SUPPRESSED, relpath=INGEST_REL))
    kept = analyze_source(INGEST_HOT_LOOP_SUPPRESSED, relpath=INGEST_REL,
                          keep_suppressed=True)
    assert "host-sync-in-jit" in names(kept)
    assert "host-sync-in-jit" not in names(
        analyze_source(INGEST_HOT_LOOP_CLEAN, relpath=INGEST_REL))


INGEST_SHARED_SUPPRESSED = """
LAST_INGEST_STATS = {}

def update(stats):
    LAST_INGEST_STATS["x"] = stats  # tpu-lint: disable=unlocked-shared-state
"""


def test_ingest_shared_state_trio():
    # fires: stats-dict mutation without the lock, inside the new scope
    assert "unlocked-shared-state" in names(
        analyze_source(SHARED_BAD, relpath=INGEST_REL))
    # suppressed inline with a justification comment
    assert "unlocked-shared-state" not in names(
        analyze_source(INGEST_SHARED_SUPPRESSED, relpath=INGEST_REL))
    assert "unlocked-shared-state" in names(
        analyze_source(INGEST_SHARED_SUPPRESSED, relpath=INGEST_REL,
                       keep_suppressed=True))
    # clean: the same mutation under the module lock
    assert "unlocked-shared-state" not in names(
        analyze_source(SHARED_LOCKED, relpath=INGEST_REL))


# ---- telemetry-schema ----

def test_telemetry_schema_fires_on_unregistered_type():
    src = ('from .obs import emit\n'
           'def f():\n'
           '    emit("not_a_registered_event_type_xyz")\n')
    fs = analyze_source(src, relpath="lightgbm_tpu/somewhere.py")
    assert any(f.rule == "telemetry-schema" for f in fs)


def test_telemetry_schema_checks_fields():
    schemas = event_schemas()
    assert schemas, "EVENT_SCHEMAS literal must be extractable without import"
    etype, (required, _opt) = sorted(schemas.items())[0]
    kwargs = ", ".join(f"{k}=1" for k in sorted(required))
    ok = (f'from .obs import emit\n'
          f'def f():\n    emit("{etype}", {kwargs})\n')
    assert "telemetry-schema" not in names(
        analyze_source(ok, relpath="lightgbm_tpu/somewhere.py"))
    bad = (f'from .obs import emit\n'
           f'def f():\n    emit("{etype}", {kwargs + ", " if kwargs else ""}'
           f'bogus_field_xyz=1)\n')
    fs = analyze_source(bad, relpath="lightgbm_tpu/somewhere.py")
    assert any(f.rule == "telemetry-schema" and "bogus_field_xyz"
               in f.message for f in fs)


# ---- nonfinite-policy-literal ----

def test_nonfinite_literal_fires_and_clean():
    bad = 'params = {"nonfinite_policy": "clamp"}\n'
    fs = analyze_source(bad)
    assert any(f.rule == "nonfinite-policy-literal" for f in fs)
    ok = 'params = {"nonfinite_policy": "warn_skip_tree"}\n'
    assert "nonfinite-policy-literal" not in names(analyze_source(ok))


# ---- unsharded-transfer ----

UNSHARDED_BAD = """
import jax

def commit(chunk):
    return jax.device_put(chunk)
"""

UNSHARDED_SUPPRESSED = """
import jax

def commit(chunk):
    # legacy single-accumulator path  # tpu-lint: disable=unsharded-transfer
    return jax.device_put(chunk)
"""

UNSHARDED_CLEAN = """
import jax

def commit(chunk, plan, shard, sharding):
    a = jax.device_put(chunk, plan.devices[shard])
    b = jax.device_put(chunk, device=plan.devices[shard])
    return a, jax.device_put(chunk, sharding=sharding), b
"""

MESH_REL = "lightgbm_tpu/ingest.py"


def test_unsharded_transfer_fires_in_mesh_scope():
    assert "unsharded-transfer" in names(
        analyze_source(UNSHARDED_BAD, relpath=MESH_REL))
    assert "unsharded-transfer" in names(
        analyze_source(UNSHARDED_BAD, relpath="lightgbm_tpu/parallel/mesh.py"))


def test_unsharded_transfer_out_of_scope_silent():
    # a default placement outside the mesh layer is fine (serving, tests)
    assert "unsharded-transfer" not in names(
        analyze_source(UNSHARDED_BAD, relpath="lightgbm_tpu/engine.py"))


def test_unsharded_transfer_suppressed():
    assert "unsharded-transfer" not in names(
        analyze_source(UNSHARDED_SUPPRESSED, relpath=MESH_REL))
    kept = analyze_source(UNSHARDED_SUPPRESSED, relpath=MESH_REL,
                          keep_suppressed=True)
    assert "unsharded-transfer" in names(kept)


def test_unsharded_transfer_clean_with_placement():
    assert "unsharded-transfer" not in names(
        analyze_source(UNSHARDED_CLEAN, relpath=MESH_REL))


# ---- swallowed-device-error ----

SWALLOWED_BAD = """
import jax

def upload(chunk, dev):
    try:
        x = jax.device_put(chunk, dev)
        x.block_until_ready()
    except Exception as e:
        log.debug("upload failed: %s", e)
"""

SWALLOWED_SUPPRESSED = """
import jax

def probe(x):
    try:
        jax.device_put(x).block_until_ready()
    except Exception as e:   # tpu-lint: disable=swallowed-device-error
        log.debug("probe failed: %s", e)
"""

SWALLOWED_CLEAN = """
import jax
from .utils.retry import call_with_backoff

def upload(chunk, dev, _fail):
    try:
        return jax.device_put(chunk, dev)
    except Exception as e:
        _fail(e)                      # stash-and-surface handoff

def upload_retry(chunk, dev):
    return call_with_backoff(lambda: jax.device_put(chunk, dev))

def upload_emit(chunk, dev):
    try:
        return jax.device_put(chunk, dev)
    except Exception as e:
        emit("device_fault", point="h2d", policy="fatal", action="fatal")
        raise

def narrow(chunk, dev):
    try:
        return jax.device_put(chunk, dev)
    except TypeError:
        return None
"""

PRODUCT_REL = "lightgbm_tpu/serving.py"


def test_swallowed_device_error_fires():
    fs = analyze_source(SWALLOWED_BAD, relpath=PRODUCT_REL)
    assert any(f.rule == "swallowed-device-error" for f in fs)
    # bare except and tuple forms count as broad too
    bare = SWALLOWED_BAD.replace("except Exception as e:", "except:")
    bare = bare.replace('log.debug("upload failed: %s", e)', "pass")
    assert "swallowed-device-error" in names(
        analyze_source(bare, relpath=PRODUCT_REL))
    tup = SWALLOWED_BAD.replace("except Exception as e:",
                                "except (ValueError, XlaRuntimeError) as e:")
    assert "swallowed-device-error" in names(
        analyze_source(tup, relpath=PRODUCT_REL))


def test_swallowed_device_error_out_of_scope_silent():
    # tests/scripts may swallow freely; so does the analyzer itself
    assert "swallowed-device-error" not in names(
        analyze_source(SWALLOWED_BAD, relpath="tests/test_something.py"))
    assert "swallowed-device-error" not in names(
        analyze_source(SWALLOWED_BAD,
                       relpath="lightgbm_tpu/analysis/core.py"))


def test_swallowed_device_error_suppressed():
    assert "swallowed-device-error" not in names(
        analyze_source(SWALLOWED_SUPPRESSED, relpath=PRODUCT_REL))
    kept = analyze_source(SWALLOWED_SUPPRESSED, relpath=PRODUCT_REL,
                          keep_suppressed=True)
    assert "swallowed-device-error" in names(kept)


def test_swallowed_device_error_clean_escape_hatches():
    # handoff / retry / emit+reraise / narrow except are all acceptable
    assert "swallowed-device-error" not in names(
        analyze_source(SWALLOWED_CLEAN, relpath=PRODUCT_REL))


# ---------------------------------------------------------------------------
# suppression / baseline machinery

def test_standalone_suppression_comment_covers_next_line():
    src = ('import jax\n'
           'def build(x):\n'
           '    # tpu-lint: disable=retrace-hazard\n'
           '    f = jax.jit(lambda a: a + 1)\n'
           '    return f(x)\n')
    assert "retrace-hazard" not in names(analyze_source(src))


def test_file_level_suppression():
    src = ('# tpu-lint: disable-file=retrace-hazard\n'
           'import jax\n'
           'def build(x):\n'
           '    return jax.jit(lambda a: a + 1)(x)\n')
    assert "retrace-hazard" not in names(analyze_source(src))


def test_unknown_rule_name_raises():
    with pytest.raises(KeyError):
        analyze_source("x = 1\n", rules=["no-such-rule"])


# ---------------------------------------------------------------------------
# whole-repo gate + reporters + speed + jax-freedom

@pytest.fixture(scope="module")
def repo_scan():
    """ONE timed whole-repo scan shared by the gate/baseline/reporter tests:
    four identical full scans were pure repetition (~15s of tier-1 wall on
    the 1-core box). Returns (result, wall_seconds)."""
    t0 = time.perf_counter()
    res = analyze_paths(baseline_path=DEFAULT_BASELINE)
    return res, time.perf_counter() - t0


def test_baseline_is_empty_by_policy(repo_scan):
    """The v2 triage burned the baseline to zero: every historical finding
    is now either fixed or suppressed INLINE at the site with its
    justification next to the code it excuses. New findings must follow the
    same path — the baseline is a migration mechanism, not a dumping
    ground, and it stays empty."""
    entries = load_baseline(DEFAULT_BASELINE)
    assert entries == [], \
        ("baseline.json grew entries again — fix the finding or move the "
         "justification inline (# tpu-lint: disable=<rule>): "
         + ", ".join(f"{e.path}:{e.line} {e.rule}" for e in entries))
    res, _ = repo_scan
    assert not res.stale_baseline
    assert not res.baselined


def test_repo_is_clean_and_fast(repo_scan):
    res, elapsed = repo_scan
    assert not res.parse_errors, [f.render() for f in res.parse_errors]
    assert not res.findings, [f.render() for f in res.findings]
    assert not res.stale_baseline
    assert res.files > 50        # the scan surface really is the whole repo
    assert elapsed < 10.0, f"lint took {elapsed:.1f}s; tier-1 budget is 10s"


def test_json_reporter_shape(repo_scan):
    res, _ = repo_scan
    doc = json.loads(render_json(res))
    assert doc["version"] == 2
    assert doc["summary"]["ok"] is True
    for key in ("files", "findings", "errors", "warnings", "threshold",
                "suppressed", "baselined", "stale_baseline", "elapsed_s"):
        assert key in doc["summary"]
    assert isinstance(doc["findings"], list)


def test_every_rule_is_documented():
    doc_path = os.path.join(REPO_ROOT, "docs", "STATIC_ANALYSIS.md")
    text = open(doc_path).read()
    for name, rule in all_rules().items():
        assert f"`{name}`" in text, f"rule {name} missing from {doc_path}"
        assert rule.description and rule.rationale


def test_cli_runs_jax_free():
    """The CI entry point must analyze the whole repo without jax ever
    entering sys.modules (LGBMTPU_LINT_ONLY short-circuits the package
    import). One subprocess, asserted from the inside."""
    code = (
        "import json, os, sys\n"
        "os.environ['LGBMTPU_LINT_ONLY'] = '1'\n"
        "from lightgbm_tpu.analysis import main\n"
        "rc = main(['--format=json'])\n"
        "assert rc == 0, 'lint failed'\n"
        "bad = [m for m in sys.modules if m == 'jax' or "
        "m.startswith('jax.')]\n"
        "assert not bad, f'jax leaked into the lint pass: {bad[:3]}'\n"
    )
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO_ROOT,
                          capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_schema_shim_still_works():
    """scripts/check_telemetry_schema.py kept its main()->0 contract after
    migrating into the rule registry (test_observability.py exec's it by
    path; this covers the direct-subprocess surface)."""
    script = os.path.join(REPO_ROOT, "scripts", "check_telemetry_schema.py")
    proc = subprocess.run([sys.executable, script], capture_output=True,
                          text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "PASS" in proc.stdout


# ---- serving-scheduler rule scopes (PR: online serving) ----
# server.py (the microbatch scheduler) is multi-threaded, so both threading
# rules extend their scope to it, and the scheduler loop gets a stricter
# audit: blocking-call-in-scheduler-loop — one thread drains the shared
# request queue, so ANY blocking call there (time.sleep, unbounded .join(),
# .get() with no timeout) stalls every queued request, not just its own.

SERVER_REL = "lightgbm_tpu/server.py"

SCHED_LOOP_BAD = """
import time

def _scheduler_loop(self):
    while True:
        req = self._q.get()
        time.sleep(0.001)
        self._worker.join()
        self._flush([req])
"""

SCHED_LOOP_SUPPRESSED = """
import time

def _scheduler_loop(self):
    while True:
        req = self._q.get(timeout=0.05)
        # single-request debug build: the pause IS the batching window
        time.sleep(0.001)   # tpu-lint: disable=host-sync-in-jit
        self._flush([req])
"""

SCHED_LOOP_CLEAN = """
import queue

def _scheduler_loop(self):
    while True:
        try:
            req = self._q.get(timeout=0.05)
        except queue.Empty:
            continue
        try:
            nxt = self._q.get_nowait()
        except queue.Empty:
            nxt = None
        self._flush([r for r in (req, nxt) if r is not None])
"""


def test_scheduler_loop_blocking_calls_fire():
    found = names(analyze_source(SCHED_LOOP_BAD, relpath=SERVER_REL))
    assert "host-sync-in-jit" in found
    msgs = [f.message for f in analyze_source(SCHED_LOOP_BAD,
                                              relpath=SERVER_REL)
            if f.rule == "host-sync-in-jit"]
    # all three blocking shapes are called out: sleep, bare join, bare get
    assert any("sleep" in m for m in msgs), msgs
    assert any(".join()" in m for m in msgs), msgs
    assert any(".get()" in m for m in msgs), msgs
    # the very same loop body outside the designated module is not audited
    assert "host-sync-in-jit" not in names(
        analyze_source(SCHED_LOOP_BAD, relpath="lightgbm_tpu/engine.py"))


def test_scheduler_loop_suppressed_and_clean():
    assert "host-sync-in-jit" not in names(
        analyze_source(SCHED_LOOP_SUPPRESSED, relpath=SERVER_REL))
    kept = analyze_source(SCHED_LOOP_SUPPRESSED, relpath=SERVER_REL,
                          keep_suppressed=True)
    assert "host-sync-in-jit" in names(kept)
    assert "host-sync-in-jit" not in names(
        analyze_source(SCHED_LOOP_CLEAN, relpath=SERVER_REL))


SERVER_SHARED_BAD = """
_LAST_SERVER = {}

def remember(srv):
    _LAST_SERVER["srv"] = srv
"""

SERVER_SHARED_LOCKED = """
import threading
_LAST_SERVER = {}
_LOCK = threading.Lock()

def remember(srv):
    with _LOCK:
        _LAST_SERVER["srv"] = srv
"""


def test_server_module_in_shared_state_scope():
    assert "unlocked-shared-state" in names(
        analyze_source(SERVER_SHARED_BAD, relpath=SERVER_REL))
    assert "unlocked-shared-state" not in names(
        analyze_source(SERVER_SHARED_LOCKED, relpath=SERVER_REL))


# ---- online-trainer rule scopes (PR: continuous training) ----
# online.py's run() loop drains a shared batch source the same way the
# microbatch scheduler drains its queue — one loop, many buffered batches
# behind it — so it joins both the scheduler-loop audit (no sleep, no bare
# join/get) and the shared-state scope (the module-level cycle stats).

ONLINE_REL = "lightgbm_tpu/online.py"

ONLINE_RUN_BAD = """
import time

def run(self, source, stop):
    while not stop.is_set():
        batch = self._q.get()
        time.sleep(0.05)
        self._worker.join()
        self.feed(*batch)
"""

ONLINE_RUN_SUPPRESSED = """
import time

def run(self, source, stop):
    while not stop.is_set():
        batch = source()
        if batch is None:
            # offline replay harness: pacing the feed IS the simulation
            time.sleep(0.05)   # tpu-lint: disable=host-sync-in-jit
            continue
        self.feed(*batch)
"""

ONLINE_RUN_CLEAN = """
def run(self, source, stop):
    while not stop.is_set():
        batch = source()
        if batch is None:
            stop.wait(0.05)
            continue
        self.feed(*batch)
"""


def test_online_run_loop_blocking_calls_fire():
    found = analyze_source(ONLINE_RUN_BAD, relpath=ONLINE_REL)
    assert "host-sync-in-jit" in names(found)
    msgs = [f.message for f in found if f.rule == "host-sync-in-jit"]
    assert any("sleep" in m for m in msgs), msgs
    assert any(".join()" in m for m in msgs), msgs
    assert any(".get()" in m for m in msgs), msgs
    # run() elsewhere is not a designated scheduler loop
    assert "host-sync-in-jit" not in names(
        analyze_source(ONLINE_RUN_BAD, relpath="lightgbm_tpu/basic.py"))


def test_online_run_loop_suppressed_and_clean():
    assert "host-sync-in-jit" not in names(
        analyze_source(ONLINE_RUN_SUPPRESSED, relpath=ONLINE_REL))
    kept = analyze_source(ONLINE_RUN_SUPPRESSED, relpath=ONLINE_REL,
                          keep_suppressed=True)
    assert "host-sync-in-jit" in names(kept)
    # the shipped idiom — wait on the stop event, bounded — is clean
    assert "host-sync-in-jit" not in names(
        analyze_source(ONLINE_RUN_CLEAN, relpath=ONLINE_REL))


ONLINE_STATS_BAD = """
LAST_CYCLE_STATS = {}

def record(stats):
    LAST_CYCLE_STATS.clear()
    LAST_CYCLE_STATS.update(stats)
"""

ONLINE_STATS_LOCKED = """
import threading
_STATS_LOCK = threading.Lock()
LAST_CYCLE_STATS = {}

def record(stats):
    with _STATS_LOCK:
        LAST_CYCLE_STATS.clear()
        LAST_CYCLE_STATS.update(stats)
"""


def test_online_module_in_shared_state_scope():
    found = analyze_source(ONLINE_STATS_BAD, relpath=ONLINE_REL)
    assert names(found).count("unlocked-shared-state") == 2   # clear + update
    assert "unlocked-shared-state" not in names(
        analyze_source(ONLINE_STATS_LOCKED, relpath=ONLINE_REL))
    # outside the threaded scope the same mutation is the normal idiom
    assert "unlocked-shared-state" not in names(
        analyze_source(ONLINE_STATS_BAD, relpath="lightgbm_tpu/basic.py"))


# ---- observability-plane rule scopes (PR: live obs plane) ----
# The obs plane added modules that EMIT real telemetry (slo.py, flight.py,
# http_server.py) and a background flusher loop (obs/__init__._flush_loop):
# the telemetry-schema skip list narrows from all of obs/ to just the
# plumbing files, and the flusher joins the scheduler-loop audit (it must
# wait on its stop event, never a bare sleep).

OBS_INIT_REL = "lightgbm_tpu/obs/__init__.py"

FLUSH_LOOP_BAD = """
import time

def _flush_loop(interval_s, stop):
    while not stop.is_set():
        time.sleep(interval_s)
        export_all()
"""

FLUSH_LOOP_SUPPRESSED = """
import time

def _flush_loop(interval_s, stop):
    while not stop.is_set():
        # simulation harness: wall-clock pacing IS the experiment
        time.sleep(interval_s)   # tpu-lint: disable=host-sync-in-jit
        export_all()
"""

FLUSH_LOOP_CLEAN = """
def _flush_loop(interval_s, stop):
    while not stop.wait(interval_s):
        export_all()
"""


def test_flush_loop_blocking_calls_fire():
    found = analyze_source(FLUSH_LOOP_BAD, relpath=OBS_INIT_REL)
    assert any(f.rule == "host-sync-in-jit" and "sleep" in f.message
               for f in found)
    # _flush_loop elsewhere is not a designated scheduler loop
    assert "host-sync-in-jit" not in names(
        analyze_source(FLUSH_LOOP_BAD, relpath="lightgbm_tpu/basic.py"))


def test_flush_loop_suppressed_and_clean():
    assert "host-sync-in-jit" not in names(
        analyze_source(FLUSH_LOOP_SUPPRESSED, relpath=OBS_INIT_REL))
    assert "host-sync-in-jit" in names(
        analyze_source(FLUSH_LOOP_SUPPRESSED, relpath=OBS_INIT_REL,
                       keep_suppressed=True))
    # the shipped idiom — wait on the stop event, bounded — is clean
    assert "host-sync-in-jit" not in names(
        analyze_source(FLUSH_LOOP_CLEAN, relpath=OBS_INIT_REL))


OBS_EMIT_BAD = """
def dump(reason):
    from . import emit
    emit("flight_dump", reason=reason, events=1, bogus_field_xyz=2)
"""

OBS_EMIT_SUPPRESSED = """
def dump(reason):
    from . import emit
    emit("flight_dump", reason=reason, events=1, bogus_field_xyz=2)  # tpu-lint: disable=telemetry-schema
"""

OBS_EMIT_CLEAN = """
def dump(reason):
    from . import emit
    emit("flight_dump", reason=reason, events=1, spans=0, path="p")
"""


def test_telemetry_schema_covers_obs_emitting_modules():
    # the emitting obs modules are IN scope after the skip-list narrowing
    for rel in ("lightgbm_tpu/obs/flight.py", "lightgbm_tpu/obs/slo.py",
                "lightgbm_tpu/obs/http_server.py"):
        fs = analyze_source(OBS_EMIT_BAD, relpath=rel)
        assert any(f.rule == "telemetry-schema" and "bogus_field_xyz"
                   in f.message for f in fs), rel
    assert "telemetry-schema" not in names(
        analyze_source(OBS_EMIT_SUPPRESSED,
                       relpath="lightgbm_tpu/obs/flight.py"))
    assert "telemetry-schema" in names(
        analyze_source(OBS_EMIT_SUPPRESSED,
                       relpath="lightgbm_tpu/obs/flight.py",
                       keep_suppressed=True))
    assert "telemetry-schema" not in names(
        analyze_source(OBS_EMIT_CLEAN, relpath="lightgbm_tpu/obs/flight.py"))


def test_telemetry_schema_still_skips_obs_plumbing():
    # the delegating emit wrapper (non-literal etype) lives in plumbing
    # modules that stay out of scope
    wrapper = ('def emit(etype, **fields):\n'
               '    EVENTS.emit(etype, **fields)\n')
    for rel in ("lightgbm_tpu/obs/__init__.py",
                "lightgbm_tpu/obs/events.py"):
        assert "telemetry-schema" not in names(
            analyze_source(wrapper, relpath=rel)), rel
    # the same dynamic-etype call in an emitting obs module DOES fire
    assert "telemetry-schema" in names(
        analyze_source(wrapper, relpath="lightgbm_tpu/obs/flight.py"))


OBS_SERVER_SINGLETON_BAD = """
_SERVER = None

def maybe_start(conf):
    global _SERVER
    _SERVER = build(conf)
    return _SERVER
"""

OBS_SERVER_SINGLETON_LOCKED = """
import threading
_server_lock = threading.Lock()
_SERVER = None

def maybe_start(conf):
    global _SERVER
    with _server_lock:
        _SERVER = build(conf)
        return _SERVER
"""


def test_obs_http_singleton_in_shared_state_scope():
    rel = "lightgbm_tpu/obs/http_server.py"
    assert "unlocked-shared-state" in names(
        analyze_source(OBS_SERVER_SINGLETON_BAD, relpath=rel))
    assert "unlocked-shared-state" not in names(
        analyze_source(OBS_SERVER_SINGLETON_LOCKED, relpath=rel))


# ---------------------------------------------------------------------------
# v2: dataflow-aware rule families (lock-order / donation-safety /
# collective-consistency), the severity threshold, changed-only + SARIF,
# and the rule-coverage meta-test. compile-budget's fixtures live in
# tests/test_compile_budget.py (they exercise the dynamic probe machinery).

SERVE_REL = "lightgbm_tpu/server.py"   # lock rules scope to the serve stack

LOCK_CYCLE_FIRE = """
import threading

_REG_LOCK = threading.Lock()
_STATS_LOCK = threading.Lock()

def publish(model):
    with _REG_LOCK:
        with _STATS_LOCK:
            return model

def snapshot():
    with _STATS_LOCK:
        with _REG_LOCK:
            return 1
"""

LOCK_CYCLE_SUPPRESSED = "# tpu-lint: disable-file=lock-order\n" \
    + LOCK_CYCLE_FIRE

LOCK_CYCLE_CLEAN = """
import threading

_REG_LOCK = threading.Lock()
_STATS_LOCK = threading.Lock()

def publish(model):
    with _REG_LOCK:
        with _STATS_LOCK:
            return model

def snapshot():
    with _REG_LOCK:
        with _STATS_LOCK:
            return 1
"""

LOCK_SELF_DEADLOCK_FIRE = """
import threading

_REG_LOCK = threading.Lock()

def refresh():
    with _REG_LOCK:
        return rebuild()

def rebuild():
    with _REG_LOCK:
        return 2
"""

LOCK_SELF_DEADLOCK_RLOCK_CLEAN = """
import threading

_REG_LOCK = threading.RLock()

def refresh():
    with _REG_LOCK:
        return rebuild()

def rebuild():
    with _REG_LOCK:
        return 2
"""

CHECK_THEN_ACT_FIRE = """
import threading

_LOCK = threading.Lock()
_STATE = {}

def bump(key, delta):
    with _LOCK:
        cur = _STATE.get(key, 0)
    with _LOCK:
        _STATE[key] = cur + delta
"""

CHECK_THEN_ACT_SUPPRESSED = """
import threading

_LOCK = threading.Lock()
_STATE = {}

def bump(key, delta):
    with _LOCK:
        cur = _STATE.get(key, 0)
    with _LOCK:  # tpu-lint: disable=lock-order
        _STATE[key] = cur + delta
"""

CHECK_THEN_ACT_CLEAN = """
import threading

_LOCK = threading.Lock()
_STATE = {}

def bump(key, delta):
    with _LOCK:
        cur = _STATE.get(key, 0)
        _STATE[key] = cur + delta
"""


def test_lock_order_cycle_fires():
    fs = analyze_source(LOCK_CYCLE_FIRE, relpath=SERVE_REL,
                        rules=["lock-order"])
    assert "lock-order" in names(fs)
    msg = [f for f in fs if "cycle" in f.message][0]
    assert "potential deadlock" in msg.message
    assert msg.severity == "error"


def test_lock_order_cycle_suppressed_and_clean():
    assert "lock-order" not in names(
        analyze_source(LOCK_CYCLE_SUPPRESSED, relpath=SERVE_REL,
                       rules=["lock-order"]))
    assert "lock-order" not in names(
        analyze_source(LOCK_CYCLE_CLEAN, relpath=SERVE_REL,
                       rules=["lock-order"]))


def test_lock_order_self_deadlock_through_callee():
    fs = analyze_source(LOCK_SELF_DEADLOCK_FIRE, relpath=SERVE_REL,
                        rules=["lock-order"])
    assert any("self-deadlock" in f.message for f in fs)
    # the same shape on an RLock is legal re-entry
    assert "lock-order" not in names(
        analyze_source(LOCK_SELF_DEADLOCK_RLOCK_CLEAN, relpath=SERVE_REL,
                       rules=["lock-order"]))


def test_lock_order_out_of_scope_module_not_flagged():
    assert "lock-order" not in names(
        analyze_source(LOCK_CYCLE_FIRE, relpath="lightgbm_tpu/binning.py",
                       rules=["lock-order"]))


def test_check_then_act_trio():
    fs = analyze_source(CHECK_THEN_ACT_FIRE, relpath=SERVE_REL,
                        rules=["lock-order"])
    assert any("check-then-act" in f.message for f in fs)
    assert all(f.severity == "warning" for f in fs)
    assert "lock-order" not in names(
        analyze_source(CHECK_THEN_ACT_SUPPRESSED, relpath=SERVE_REL,
                       rules=["lock-order"]))
    assert "lock-order" not in names(
        analyze_source(CHECK_THEN_ACT_CLEAN, relpath=SERVE_REL,
                       rules=["lock-order"]))


# ---- fleet rule scopes (PR: serving fleet) ----
# lightgbm_tpu/fleet/ is the third deliberately multi-threaded subsystem
# (balancer threads, the health-probe loop, the rollout state machine), so
# the threading rules extend their scope to it: unlocked-shared-state and
# lock-order cover the whole fleet/ directory, and the replica health
# prober joins the scheduler-loop audit (waiting belongs on the stop
# event, never a bare sleep). Each scope extension gets its own
# fire / suppressed / clean trio.

FLEET_ROLLOUT_REL = "lightgbm_tpu/fleet/rollout.py"
FLEET_REPLICA_REL = "lightgbm_tpu/fleet/replica.py"
FLEET_SERVICE_REL = "lightgbm_tpu/fleet/service.py"

FLEET_SHARED_FIRE = """
_ROLLOUT_HISTORY = []

def record(event):
    _ROLLOUT_HISTORY.append(event)
"""

FLEET_SHARED_SUPPRESSED = """
_ROLLOUT_HISTORY = []

def record(event):
    # single writer: only the scheduler thread records transitions
    _ROLLOUT_HISTORY.append(event)  # tpu-lint: disable=unlocked-shared-state
"""

FLEET_SHARED_CLEAN = """
import threading

_ROLLOUT_HISTORY = []
_lock = threading.Lock()

def record(event):
    with _lock:
        _ROLLOUT_HISTORY.append(event)
"""


def test_fleet_shared_state_trio():
    assert "unlocked-shared-state" in names(
        analyze_source(FLEET_SHARED_FIRE, relpath=FLEET_ROLLOUT_REL))
    assert "unlocked-shared-state" not in names(
        analyze_source(FLEET_SHARED_SUPPRESSED, relpath=FLEET_ROLLOUT_REL))
    assert "unlocked-shared-state" in names(
        analyze_source(FLEET_SHARED_SUPPRESSED, relpath=FLEET_ROLLOUT_REL,
                       keep_suppressed=True))
    assert "unlocked-shared-state" not in names(
        analyze_source(FLEET_SHARED_CLEAN, relpath=FLEET_ROLLOUT_REL))
    # same mutation outside the fleet/ scope is the normal idiom
    assert "unlocked-shared-state" not in names(
        analyze_source(FLEET_SHARED_FIRE, relpath="lightgbm_tpu/tree.py"))


FLEET_PROBE_FIRE = """
import time

def _probe_loop(self):
    while not self._stop.is_set():
        time.sleep(self._interval)
        self.check_health()
"""

FLEET_PROBE_SUPPRESSED = """
import time

def _probe_loop(self):
    while not self._stop.is_set():
        # probe-interval test double: exact wall pause wanted
        time.sleep(self._interval)  # tpu-lint: disable=host-sync-in-jit
        self.check_health()
"""

FLEET_PROBE_CLEAN = """
def _probe_loop(self):
    while not self._stop.wait(self._interval):
        self.check_health()
"""


def test_fleet_probe_loop_trio():
    fs = analyze_source(FLEET_PROBE_FIRE, relpath=FLEET_REPLICA_REL)
    assert "host-sync-in-jit" in names(fs)
    assert any("sleep" in f.message for f in fs)
    assert "host-sync-in-jit" not in names(
        analyze_source(FLEET_PROBE_SUPPRESSED, relpath=FLEET_REPLICA_REL))
    assert "host-sync-in-jit" in names(
        analyze_source(FLEET_PROBE_SUPPRESSED, relpath=FLEET_REPLICA_REL,
                       keep_suppressed=True))
    assert "host-sync-in-jit" not in names(
        analyze_source(FLEET_PROBE_CLEAN, relpath=FLEET_REPLICA_REL))
    # only the designated (path, function) pair is audited: the same loop
    # under a different name, or in a module outside the list, passes
    src_other_fn = FLEET_PROBE_FIRE.replace("_probe_loop", "_poll_once")
    assert "host-sync-in-jit" not in names(
        analyze_source(src_other_fn, relpath=FLEET_REPLICA_REL))
    assert "host-sync-in-jit" not in names(
        analyze_source(FLEET_PROBE_FIRE, relpath="lightgbm_tpu/engine.py"))


FLEET_LOCK_FIRE = """
import threading

_POOL_LOCK = threading.Lock()
_ROLLOUT_LOCK = threading.Lock()

def publish_all(model):
    with _POOL_LOCK:
        with _ROLLOUT_LOCK:
            return model

def promote():
    with _ROLLOUT_LOCK:
        with _POOL_LOCK:
            return 1
"""

FLEET_LOCK_SUPPRESSED = "# tpu-lint: disable-file=lock-order\n" \
    + FLEET_LOCK_FIRE

FLEET_LOCK_CLEAN = """
import threading

_POOL_LOCK = threading.Lock()
_ROLLOUT_LOCK = threading.Lock()

def publish_all(model):
    with _POOL_LOCK:
        with _ROLLOUT_LOCK:
            return model

def promote():
    with _POOL_LOCK:
        with _ROLLOUT_LOCK:
            return 1
"""


def test_fleet_lock_order_trio():
    fs = analyze_source(FLEET_LOCK_FIRE, relpath=FLEET_SERVICE_REL,
                        rules=["lock-order"])
    assert "lock-order" in names(fs)
    assert any("cycle" in f.message for f in fs)
    assert "lock-order" not in names(
        analyze_source(FLEET_LOCK_SUPPRESSED, relpath=FLEET_SERVICE_REL,
                       rules=["lock-order"]))
    assert "lock-order" not in names(
        analyze_source(FLEET_LOCK_CLEAN, relpath=FLEET_SERVICE_REL,
                       rules=["lock-order"]))
    # fleet/ is in scope; the same cycle elsewhere is not audited
    assert "lock-order" not in names(
        analyze_source(FLEET_LOCK_FIRE, relpath="lightgbm_tpu/binning.py",
                       rules=["lock-order"]))


# ---- donation-safety ----

DONATION_FIRE = """
import jax

_FUSED = jax.jit(lambda a, b: a + b, donate_argnums=(0,))

def step(acc, upd):
    out = _FUSED(acc, upd)
    return out + acc.sum()
"""

DONATION_SUPPRESSED = """
import jax

_FUSED = jax.jit(lambda a, b: a + b, donate_argnums=(0,))

def step(acc, upd):
    out = _FUSED(acc, upd)
    return out + acc.sum()  # tpu-lint: disable=donation-safety
"""

DONATION_CLEAN_REBIND = """
import jax

_FUSED = jax.jit(lambda a, b: a + b, donate_argnums=(0,))

def step(acc, upd):
    acc = _FUSED(acc, upd)
    return acc.sum()

def run(items, acc):
    for u in items:
        acc = _FUSED(acc, u)
    return acc
"""

DONATION_LOOP_FIRE = """
import jax

_FUSED = jax.jit(lambda a, b: a + b, donate_argnums=(0,))

def run(items, acc):
    for u in items:
        probe = acc.sum()
        out = _FUSED(acc, u)
    return out
"""

DONATION_DECORATOR_FIRE = """
import jax
from functools import partial

@partial(jax.jit, donate_argnums=(0,))
def fused(a, b):
    return a + b

def step(acc, upd):
    out = fused(acc, upd)
    return out + acc.sum()
"""


def test_donation_safety_trio():
    fs = analyze_source(DONATION_FIRE, rules=["donation-safety"])
    assert names(fs) == ["donation-safety"]
    assert "donated to _FUSED()" in fs[0].message
    assert fs[0].severity == "error"
    assert "donation-safety" not in names(
        analyze_source(DONATION_SUPPRESSED, rules=["donation-safety"]))
    assert "donation-safety" not in names(
        analyze_source(DONATION_CLEAN_REBIND, rules=["donation-safety"]))


def test_donation_safety_loop_wraparound():
    """acc is donated each iteration but never rebound: the NEXT iteration
    reads a buffer the previous one invalidated."""
    assert "donation-safety" in names(
        analyze_source(DONATION_LOOP_FIRE, rules=["donation-safety"]))


def test_donation_safety_decorated_def():
    assert "donation-safety" in names(
        analyze_source(DONATION_DECORATOR_FIRE, rules=["donation-safety"]))


# ---- collective-consistency ----

COLLECTIVE_AXIS_FIRE = """
import jax

def reduce_rows(x):
    return jax.lax.psum(x, axis_name="rows")
"""

COLLECTIVE_AXIS_SUPPRESSED = """
import jax

def reduce_rows(x):
    return jax.lax.psum(x, axis_name="rows")  # tpu-lint: disable=collective-consistency
"""

COLLECTIVE_AXIS_CLEAN = """
import jax

def reduce_rows(x, axis):
    total = jax.lax.psum(x, axis_name="data")
    return total + jax.lax.psum(x, axis)
"""

CALLBACK_IN_SHARD_MAP_FIRE = """
import jax

def _grow_shard(x):
    jax.debug.print("shard sees {}", x)
    return jax.lax.psum(x, "data")

grow = jax.shard_map(_grow_shard, mesh=None, in_specs=None,
                     out_specs=None)
"""

CALLBACK_IN_SHARD_MAP_CLEAN = """
import jax

def _grow_shard(x):
    return jax.lax.psum(x, "data")

def report(x):
    jax.debug.print("host-side after the boundary {}", x)

grow = jax.shard_map(_grow_shard, mesh=None, in_specs=None,
                     out_specs=None)
"""


def test_collective_axis_trio():
    fs = analyze_source(COLLECTIVE_AXIS_FIRE,
                        rules=["collective-consistency"])
    assert names(fs) == ["collective-consistency"]
    assert "'rows'" in fs[0].message and "data" in fs[0].message
    assert fs[0].severity == "error"
    assert "collective-consistency" not in names(
        analyze_source(COLLECTIVE_AXIS_SUPPRESSED,
                       rules=["collective-consistency"]))
    assert "collective-consistency" not in names(
        analyze_source(COLLECTIVE_AXIS_CLEAN,
                       rules=["collective-consistency"]))


def test_host_callback_in_shard_map_body():
    fs = analyze_source(CALLBACK_IN_SHARD_MAP_FIRE,
                        rules=["collective-consistency"])
    assert any("once per shard" in f.message for f in fs)
    assert all(f.severity == "warning" for f in fs)
    assert "collective-consistency" not in names(
        analyze_source(CALLBACK_IN_SHARD_MAP_CLEAN,
                       rules=["collective-consistency"]))


# ---- severity threshold / changed-only / SARIF ----

def test_severity_threshold_gates_exit_semantics():
    from lightgbm_tpu.analysis.core import AnalysisResult, Finding
    warn = Finding("lock-order", "lightgbm_tpu/server.py", 1, "m", "warning")
    err = Finding("lock-order", "lightgbm_tpu/server.py", 2, "m", "error")
    base = dict(suppressed=[], baselined=[], stale_baseline=[],
                parse_errors=[], files=1, elapsed_s=0.0)
    assert AnalysisResult(findings=[warn], threshold="warn", **base).failed
    assert not AnalysisResult(findings=[warn], threshold="error",
                              **base).failed
    assert AnalysisResult(findings=[err], threshold="error", **base).failed
    r = AnalysisResult(findings=[warn, err], threshold="error", **base)
    assert [f.severity for f in r.errors] == ["error"]
    assert [f.severity for f in r.warnings] == ["warning"]


def test_changed_only_cli_runs():
    """--changed-only must work whatever the git state: dirty tree scans the
    intersection, clean tree (or no git) falls through gracefully — rc 0
    either way on a clean repo."""
    from lightgbm_tpu.analysis import main
    assert main(["--changed-only", "--format=json"]) == 0


def test_changed_files_shape():
    from lightgbm_tpu.analysis import changed_files
    files = changed_files()
    assert files is None or all(f.endswith(".py") for f in files)


def test_sarif_reporter_shape(repo_scan):
    from lightgbm_tpu.analysis import render_sarif
    res, _ = repo_scan
    doc = json.loads(render_sarif(res))
    assert doc["version"] == "2.1.0"
    run = doc["runs"][0]
    rule_ids = {r["id"] for r in run["tool"]["driver"]["rules"]}
    assert set(all_rules()) <= rule_ids
    for result in run["results"]:
        assert result["ruleId"]
        loc = result["locations"][0]["physicalLocation"]
        assert loc["artifactLocation"]["uri"]
        assert loc["region"]["startLine"] >= 1


# ---- rule coverage meta-test ----

# every registered rule -> the fixture(s) proving it fires. Dynamic rules
# are proven by named tests instead of source fixtures.
ATOMIC_WRITE_FIRE = ('def f(p, doc):\n'
                     '    with open(p, "w") as fh:\n'
                     '        fh.write(doc)\n')
NONFINITE_LITERAL_FIRE = 'params = {"nonfinite_policy": "clamp"}\n'
UNREGISTERED_PARAM_FIRE = ('def f(params):\n'
                           '    return params.get("no_such_knob_xyz", 3)\n')
TELEMETRY_SCHEMA_FIRE = ('from .obs import emit\n'
                         'def f():\n'
                         '    emit("not_a_registered_event_type_xyz")\n')

RULE_FIXTURES = {
    "host-sync-in-jit": [("HOST_SYNC_BAD", None),
                         ("INGEST_HOT_LOOP_BAD", "lightgbm_tpu/ingest.py"),
                         ("FLEET_PROBE_FIRE", FLEET_REPLICA_REL)],
    "retrace-hazard": [("RETRACE_JIT_IN_FN", None),
                       ("RETRACE_ARGNUMS_OOR", None)],
    "dtype-drift": [("DTYPE_BAD", None),
                    ("DTYPE_I64_BAD", None)],
    "unlocked-shared-state": [("SHARED_BAD", "lightgbm_tpu/serving.py"),
                              ("FLEET_SHARED_FIRE", FLEET_ROLLOUT_REL)],
    "unsharded-transfer": [("UNSHARDED_BAD", "lightgbm_tpu/ingest.py")],
    "swallowed-device-error": [("SWALLOWED_BAD", "lightgbm_tpu/serving.py")],
    "non-atomic-artifact-write": [("ATOMIC_WRITE_FIRE", None)],
    "nonfinite-policy-literal": [("NONFINITE_LITERAL_FIRE", None)],
    "nonfinite-policy-smoke": "dynamic: exercised by --dynamic runs and "
                              "the obs-plane nonfinite tests",
    "unregistered-param": [("UNREGISTERED_PARAM_FIRE", None)],
    "telemetry-schema": [("TELEMETRY_SCHEMA_FIRE",
                          "lightgbm_tpu/somewhere.py")],
    "lock-order": [("LOCK_CYCLE_FIRE", SERVE_REL),
                   ("LOCK_SELF_DEADLOCK_FIRE", SERVE_REL),
                   ("CHECK_THEN_ACT_FIRE", SERVE_REL),
                   ("FLEET_LOCK_FIRE", FLEET_SERVICE_REL)],
    "donation-safety": [("DONATION_FIRE", None)],
    "collective-consistency": [("COLLECTIVE_AXIS_FIRE", None),
                               ("CALLBACK_IN_SHARD_MAP_FIRE", None)],
    "compile-budget": "dynamic: tests/test_compile_budget.py",
    # SPMD pod-safety family (fixtures defined at the end of this file)
    "collective-divergence": [("COLLDIV_FIRE", None),
                              ("COLLDIV_TAINTED_FIRE", None)],
    "collective-order": [("COLLORDER_FIRE", None),
                         ("COLLORDER_TRANSITIVE_FIRE", None)],
    "wire-dtype": [("WIRE_DTYPE_FIRE", None)],
    "nonaddressable-access": [("NONADDR_FIRE", None)],
}


def test_every_rule_has_fixture_and_doc_row():
    """The registry, the doc table and the fixture battery move together:
    a new rule without a docs/STATIC_ANALYSIS.md table row and a firing
    fixture fails here, not in review."""
    doc_path = os.path.join(REPO_ROOT, "docs", "STATIC_ANALYSIS.md")
    text = open(doc_path).read()
    rules = all_rules()
    assert set(RULE_FIXTURES) == set(rules), (
        "RULE_FIXTURES out of sync with the registry: "
        f"missing={set(rules) - set(RULE_FIXTURES)} "
        f"extra={set(RULE_FIXTURES) - set(rules)}")
    g = globals()
    for name, rule in rules.items():
        assert f"| `{name}`" in text, \
            f"rule {name} has no table row in {doc_path}"
        spec = RULE_FIXTURES[name]
        if isinstance(spec, str):
            assert rule.kind == "dynamic", \
                f"{name} is static but has no source fixture"
            continue
        for fixture_name, relpath in spec:
            src = g[fixture_name]
            kwargs = {"relpath": relpath} if relpath else {}
            fired = names(analyze_source(src, rules=[name], **kwargs))
            assert name in fired, \
                f"fixture {fixture_name} no longer fires {name}"

# ---- write-ahead feed log rule scopes (PR: exactly-once online training) ----
# wal.py joins the shared-state scope (serve-handler threads append while the
# refit worker commits), online.py's _worker_loop joins the scheduler-loop
# audit (it drains the bounded trigger queue), and wal.py's append-mode log
# handle is NOT exempt from the atomic-write rule — the shipped open("ab")
# carries an inline suppression whose justification is the record framing +
# truncate-on-recovery protocol, and these fixtures keep that the only way in.

WAL_REL = "lightgbm_tpu/wal.py"

WAL_SHARED_BAD = """
_OPEN_LOGS = {}

def register_log(path, fh):
    _OPEN_LOGS[path] = fh
"""

WAL_SHARED_SUPPRESSED = """
_OPEN_LOGS = {}

def register_log(path, fh):
    # single-writer by contract: one FeedLog per trainer, opened in __init__
    _OPEN_LOGS[path] = fh   # tpu-lint: disable=unlocked-shared-state
"""

WAL_SHARED_LOCKED = """
import threading
_OPEN_LOGS = {}
_LOCK = threading.Lock()

def register_log(path, fh):
    with _LOCK:
        _OPEN_LOGS[path] = fh
"""


def test_wal_module_in_shared_state_scope():
    assert "unlocked-shared-state" in names(
        analyze_source(WAL_SHARED_BAD, relpath=WAL_REL))
    assert "unlocked-shared-state" not in names(
        analyze_source(WAL_SHARED_SUPPRESSED, relpath=WAL_REL))
    kept = analyze_source(WAL_SHARED_SUPPRESSED, relpath=WAL_REL,
                          keep_suppressed=True)
    assert "unlocked-shared-state" in names(kept)
    assert "unlocked-shared-state" not in names(
        analyze_source(WAL_SHARED_LOCKED, relpath=WAL_REL))


WORKER_LOOP_BAD = """
import time

def _worker_loop(self):
    while True:
        trigger = self._queue.get()
        time.sleep(0.1)
        self._worker.join()
        self.refit_now(trigger=trigger)
"""

WORKER_LOOP_SUPPRESSED = """
import time

def _worker_loop(self):
    while True:
        trigger = self._queue.get(timeout=0.1)
        # deterministic replay harness: the pause paces injected cycles
        time.sleep(0.1)   # tpu-lint: disable=host-sync-in-jit
        self.refit_now(trigger=trigger)
"""

WORKER_LOOP_CLEAN = """
import queue

def _worker_loop(self):
    while True:
        if self._stop.is_set():
            return
        try:
            trigger = self._queue.get(timeout=0.1)
        except queue.Empty:
            continue
        try:
            self.refit_now(trigger=trigger)
        except Exception:
            if self._stop.wait(0.05):
                return
"""


def test_refit_worker_loop_blocking_calls_fire():
    found = analyze_source(WORKER_LOOP_BAD, relpath=ONLINE_REL)
    assert "host-sync-in-jit" in names(found)
    msgs = [f.message for f in found if f.rule == "host-sync-in-jit"]
    assert any("sleep" in m for m in msgs), msgs
    assert any(".join()" in m for m in msgs), msgs
    assert any(".get()" in m for m in msgs), msgs
    # _worker_loop elsewhere is not a designated scheduler loop
    assert "host-sync-in-jit" not in names(
        analyze_source(WORKER_LOOP_BAD, relpath="lightgbm_tpu/basic.py"))


def test_refit_worker_loop_suppressed_and_clean():
    assert "host-sync-in-jit" not in names(
        analyze_source(WORKER_LOOP_SUPPRESSED, relpath=ONLINE_REL))
    kept = analyze_source(WORKER_LOOP_SUPPRESSED, relpath=ONLINE_REL,
                          keep_suppressed=True)
    assert "host-sync-in-jit" in names(kept)
    # the shipped idiom — timed get + stop-event wait, both bounded — is clean
    assert "host-sync-in-jit" not in names(
        analyze_source(WORKER_LOOP_CLEAN, relpath=ONLINE_REL))


WAL_WRITE_BAD = """
def append(self, rec):
    fh = open(self.path, "ab")
    fh.write(rec)
"""

WAL_WRITE_SUPPRESSED = """
def open_log(self):
    # append-only log: crash-safety is the framing + truncate-on-recovery
    self._fh = open(self.path, "ab")  # tpu-lint: disable=non-atomic-artifact-write
"""

WAL_WRITE_CLEAN = """
def scan(self):
    with open(self.path, "rb") as fh:
        return fh.read()
"""


def test_wal_append_write_needs_suppression():
    # wal.py is NOT an exempt module like utils/atomic_io.py: a bare
    # append-mode write there still fires, and the shipped handle must keep
    # its justified inline suppression
    assert "non-atomic-artifact-write" in names(
        analyze_source(WAL_WRITE_BAD, relpath=WAL_REL))
    assert "non-atomic-artifact-write" not in names(
        analyze_source(WAL_WRITE_SUPPRESSED, relpath=WAL_REL))
    kept = analyze_source(WAL_WRITE_SUPPRESSED, relpath=WAL_REL,
                          keep_suppressed=True)
    assert "non-atomic-artifact-write" in names(kept)
    assert "non-atomic-artifact-write" not in names(
        analyze_source(WAL_WRITE_CLEAN, relpath=WAL_REL))

# ---- delayed-label join rule scopes (PR: label-resilient training) ----
# join.py joins the shared-state scope (serve-ingress capture threads,
# label-arrival handlers, and the group's sweep thread all mutate the
# pending map), and the trainer group's _sweep_loop joins the scheduler-loop
# audit — it walks EVERY model's join buffer, so a bare sleep there delays
# both orphan expiry and shutdown across the whole group.

JOIN_REL = "lightgbm_tpu/join.py"

JOIN_SHARED_BAD = """
_PENDING_BY_NAME = {}

def register_buffer(name, buf):
    _PENDING_BY_NAME[name] = buf
"""

JOIN_SHARED_SUPPRESSED = """
_PENDING_BY_NAME = {}

def register_buffer(name, buf):
    # built once at trainer construction, read-only afterwards
    _PENDING_BY_NAME[name] = buf   # tpu-lint: disable=unlocked-shared-state
"""

JOIN_SHARED_LOCKED = """
import threading
_PENDING_BY_NAME = {}
_LOCK = threading.Lock()

def register_buffer(name, buf):
    with _LOCK:
        _PENDING_BY_NAME[name] = buf
"""


def test_join_module_in_shared_state_scope():
    assert "unlocked-shared-state" in names(
        analyze_source(JOIN_SHARED_BAD, relpath=JOIN_REL))
    assert "unlocked-shared-state" not in names(
        analyze_source(JOIN_SHARED_SUPPRESSED, relpath=JOIN_REL))
    kept = analyze_source(JOIN_SHARED_SUPPRESSED, relpath=JOIN_REL,
                          keep_suppressed=True)
    assert "unlocked-shared-state" in names(kept)
    assert "unlocked-shared-state" not in names(
        analyze_source(JOIN_SHARED_LOCKED, relpath=JOIN_REL))
    # the same mutation outside the designated scope is the normal idiom
    assert "unlocked-shared-state" not in names(
        analyze_source(JOIN_SHARED_BAD, relpath="lightgbm_tpu/basic.py"))


SWEEP_LOOP_BAD = """
import time

def _sweep_loop(self):
    while True:
        time.sleep(0.5)
        self._reaper.join()
        for tr in self.trainers():
            tr.sweep_joins()
"""

SWEEP_LOOP_SUPPRESSED = """
import time

def _sweep_loop(self):
    while not self._stop.is_set():
        # drill harness: the pause paces injected expiry rounds
        time.sleep(0.5)   # tpu-lint: disable=host-sync-in-jit
        for tr in self.trainers():
            tr.sweep_joins()
"""

SWEEP_LOOP_CLEAN = """
def _sweep_loop(self):
    while not self._stop.is_set():
        if self._stop.wait(0.5):
            return
        for tr in self.trainers():
            tr.sweep_joins()
"""


def test_group_sweep_loop_blocking_calls_fire():
    found = analyze_source(SWEEP_LOOP_BAD, relpath=ONLINE_REL)
    assert "host-sync-in-jit" in names(found)
    msgs = [f.message for f in found if f.rule == "host-sync-in-jit"]
    assert any("sleep" in m for m in msgs), msgs
    assert any(".join()" in m for m in msgs), msgs
    # _sweep_loop elsewhere is not a designated scheduler loop
    assert "host-sync-in-jit" not in names(
        analyze_source(SWEEP_LOOP_BAD, relpath="lightgbm_tpu/basic.py"))


def test_group_sweep_loop_suppressed_and_clean():
    assert "host-sync-in-jit" not in names(
        analyze_source(SWEEP_LOOP_SUPPRESSED, relpath=ONLINE_REL))
    kept = analyze_source(SWEEP_LOOP_SUPPRESSED, relpath=ONLINE_REL,
                          keep_suppressed=True)
    assert "host-sync-in-jit" in names(kept)
    # the shipped idiom — wait on the stop event, bounded — is clean
    assert "host-sync-in-jit" not in names(
        analyze_source(SWEEP_LOOP_CLEAN, relpath=ONLINE_REL))


# ---- pod multihost module scopes (PR: pod-scale multi-host training) ----
# lightgbm_tpu/parallel/multihost.py hosts the cross-process bin-sync and
# row-exchange collectives; it joins the unlocked-shared-state scope (its
# collectives run while ingest commit threads are live), stays inside the
# repo-wide swallowed-device-error scope, and its 2-D mesh work makes the
# "feature" axis a declared mesh axis. Fire / suppressed / clean per rule.

MULTIHOST_REL = "lightgbm_tpu/parallel/multihost.py"

MH_SHARED_BAD = """
_MERGED = {}

def cache_sketches(key, sketches):
    _MERGED[key] = sketches
"""

MH_SHARED_SUPPRESSED = """
_MERGED = {}

def cache_sketches(key, sketches):
    # single writer: bin finding runs before any worker thread starts
    _MERGED[key] = sketches   # tpu-lint: disable=unlocked-shared-state
"""

MH_SHARED_LOCKED = """
import threading

_MERGED = {}
_lock = threading.Lock()

def cache_sketches(key, sketches):
    with _lock:
        _MERGED[key] = sketches
"""


def test_multihost_module_in_shared_state_scope():
    assert "unlocked-shared-state" in names(
        analyze_source(MH_SHARED_BAD, relpath=MULTIHOST_REL))
    assert "unlocked-shared-state" not in names(
        analyze_source(MH_SHARED_SUPPRESSED, relpath=MULTIHOST_REL))
    kept = analyze_source(MH_SHARED_SUPPRESSED, relpath=MULTIHOST_REL,
                          keep_suppressed=True)
    assert "unlocked-shared-state" in names(kept)
    assert "unlocked-shared-state" not in names(
        analyze_source(MH_SHARED_LOCKED, relpath=MULTIHOST_REL))
    # the same mutation in a module outside every designated scope is the
    # normal single-threaded idiom
    assert "unlocked-shared-state" not in names(
        analyze_source(MH_SHARED_BAD, relpath="lightgbm_tpu/engine.py"))


MH_FEATURE_AXIS_FIRE = """
import jax

def gather_blocks(sub):
    return jax.lax.all_gather(sub, "featur", axis=2, tiled=True)
"""

MH_FEATURE_AXIS_SUPPRESSED = """
import jax

def gather_blocks(sub):
    return jax.lax.all_gather(sub, "featur", axis=2, tiled=True)  # tpu-lint: disable=collective-consistency
"""

MH_FEATURE_AXIS_CLEAN = """
import jax

def gather_blocks(sub, hist):
    j = jax.lax.axis_index("feature")
    total = jax.lax.psum(hist, axis_name="data")
    return j, jax.lax.all_gather(sub, "feature", axis=2, tiled=True)
"""


def test_collective_consistency_recognizes_feature_axis():
    """FEATURE_AXIS = "feature" in parallel/mesh.py makes the 2-D mesh axis
    a declared axis: typos fire, the real axis (and "data") stay clean."""
    from lightgbm_tpu.analysis.facts import mesh_axes
    assert {"data", "feature"} <= mesh_axes()
    fs = analyze_source(MH_FEATURE_AXIS_FIRE, relpath=MULTIHOST_REL,
                        rules=["collective-consistency"])
    assert names(fs) == ["collective-consistency"]
    assert "'featur'" in fs[0].message and "feature" in fs[0].message
    assert "collective-consistency" not in names(
        analyze_source(MH_FEATURE_AXIS_SUPPRESSED, relpath=MULTIHOST_REL,
                       rules=["collective-consistency"]))
    assert "collective-consistency" not in names(
        analyze_source(MH_FEATURE_AXIS_CLEAN, relpath=MULTIHOST_REL,
                       rules=["collective-consistency"]))


MH_SWALLOWED_BAD = """
import jax

def replicate(x, mesh):
    try:
        out = jax.device_put(x, mesh.devices.flat[0])
        out.block_until_ready()
        return out
    except Exception as e:
        log.debug("replicate failed: %s", e)
"""

MH_SWALLOWED_SUPPRESSED = """
import jax

def probe_remote(x, dev):
    try:
        jax.device_put(x, dev).block_until_ready()
    except Exception as e:   # tpu-lint: disable=swallowed-device-error
        return None
"""

MH_SWALLOWED_CLEAN = """
import jax
from ..utils.retry import call_with_backoff

def replicate(x, dev):
    return call_with_backoff(lambda: jax.device_put(x, dev),
                             name="pod replicate")
"""


def test_multihost_module_in_swallowed_device_error_scope():
    assert "swallowed-device-error" in names(
        analyze_source(MH_SWALLOWED_BAD, relpath=MULTIHOST_REL))
    assert "swallowed-device-error" not in names(
        analyze_source(MH_SWALLOWED_SUPPRESSED, relpath=MULTIHOST_REL))
    kept = analyze_source(MH_SWALLOWED_SUPPRESSED, relpath=MULTIHOST_REL,
                          keep_suppressed=True)
    assert "swallowed-device-error" in names(kept)
    # the module's actual idiom — collectives behind call_with_backoff
    assert "swallowed-device-error" not in names(
        analyze_source(MH_SWALLOWED_CLEAN, relpath=MULTIHOST_REL))


# ---- SPMD pod-safety family (PR: tpu-lint v3) ----
# Four rules over the PR 22 multi-host bug classes: a collective under
# rank-dependent control flow (deadlock-by-skipped-rendezvous), rank-divergent
# collective ORDER (silent payload corruption), raw payloads bypassing the
# multihost.py uint8 wire codec (silent f64->f32 downcast with x64 off), and
# host materialization of possibly-non-addressable arrays. Runtime
# counterpart: analysis/collectivewatch.py + the pod drill ledger checks.

COLLDIV_FIRE = """
import jax

def sync_state(x):
    from jax.experimental import multihost_utils
    if jax.process_index() == 0:
        multihost_utils.process_allgather(x)
"""

COLLDIV_TAINTED_FIRE = """
import jax

def sync_state(x, mh):
    writer = jax.process_index() == 0
    if writer:
        mh.allgather_rows(x, 10, 0)
"""

COLLDIV_SUPPRESSED = """
import jax

def sync_state(x):
    from jax.experimental import multihost_utils
    # every rank enters via the other path  # tpu-lint: disable=collective-divergence
    if jax.process_index() == 0:
        multihost_utils.process_allgather(x)
"""

COLLDIV_CLEAN = """
import jax

def sync_state(x, mh):
    if jax.process_index() == 0:
        out = mh.process_allgather(x)
    else:
        out = mh.process_allgather(x)
    return out
"""

COLLDIV_RANK_UNIFORM_CLEAN = """
import jax

def sync_state(x, mh, distributed):
    if distributed:
        return mh.process_allgather(x)
    return x
"""


def test_collective_divergence_fires():
    assert "collective-divergence" in names(analyze_source(
        COLLDIV_FIRE, rules=["collective-divergence"]))
    # one-level taint: a local assigned from process_index partitions too
    assert "collective-divergence" in names(analyze_source(
        COLLDIV_TAINTED_FIRE, rules=["collective-divergence"]))


def test_collective_divergence_suppressed():
    assert "collective-divergence" not in names(analyze_source(
        COLLDIV_SUPPRESSED, rules=["collective-divergence"]))
    kept = analyze_source(COLLDIV_SUPPRESSED,
                          rules=["collective-divergence"],
                          keep_suppressed=True)
    assert "collective-divergence" in names(kept)


def test_collective_divergence_clean():
    # every arm reaches the collective: no rank can skip the rendezvous
    assert "collective-divergence" not in names(analyze_source(
        COLLDIV_CLEAN, rules=["collective-divergence"]))
    # rank-UNIFORM condition (plain config flag): out of scope by design
    assert "collective-divergence" not in names(analyze_source(
        COLLDIV_RANK_UNIFORM_CLEAN, rules=["collective-divergence"]))


COLLORDER_FIRE = """
import jax

def exchange(x, mh):
    if jax.process_index() == 0:
        mh.process_allgather(x)
        mh.broadcast_one_to_all(x)
    else:
        mh.broadcast_one_to_all(x)
        mh.process_allgather(x)
"""

COLLORDER_SUPPRESSED = """
import jax

def exchange(x, mh):
    # tpu-lint: disable=collective-order
    if jax.process_index() == 0:
        mh.process_allgather(x)
        mh.broadcast_one_to_all(x)
    else:
        mh.broadcast_one_to_all(x)
        mh.process_allgather(x)
"""

COLLORDER_CLEAN = """
import jax

def exchange(x, mh):
    if jax.process_index() == 0:
        mh.process_allgather(x)
        mh.broadcast_one_to_all(x)
    else:
        mh.process_allgather(x)
        mh.broadcast_one_to_all(x)
"""

COLLORDER_TRANSITIVE_FIRE = """
import jax

def gather_then_bcast(x, mh):
    mh.process_allgather(x)
    mh.broadcast_one_to_all(x)

def bcast_then_gather(x, mh):
    mh.broadcast_one_to_all(x)
    mh.process_allgather(x)

def exchange(x, mh):
    if jax.process_index() == 0:
        gather_then_bcast(x, mh)
    else:
        bcast_then_gather(x, mh)
"""


def test_collective_order_fires():
    found = names(analyze_source(COLLORDER_FIRE, rules=["collective-order"]))
    assert "collective-order" in found
    # same collectives in both arms: divergence must stay quiet and leave
    # the finding to the order rule
    assert "collective-divergence" not in names(analyze_source(
        COLLORDER_FIRE, rules=["collective-divergence"]))


def test_collective_order_sees_through_the_call_graph():
    assert "collective-order" in names(analyze_source(
        COLLORDER_TRANSITIVE_FIRE, rules=["collective-order"]))


def test_collective_order_suppressed():
    assert "collective-order" not in names(analyze_source(
        COLLORDER_SUPPRESSED, rules=["collective-order"]))
    kept = analyze_source(COLLORDER_SUPPRESSED, rules=["collective-order"],
                          keep_suppressed=True)
    assert "collective-order" in names(kept)


def test_collective_order_clean():
    assert "collective-order" not in names(analyze_source(
        COLLORDER_CLEAN, rules=["collective-order"]))


# the seeded PR 22 regression: the ORIGINAL allgather_sketches shape — an
# f64 sketch vector handed straight to process_allgather, where x64-disabled
# jax rounds it through f32 and bin bounds stop being byte-identical
WIRE_DTYPE_FIRE = """
import numpy as np

def allgather_sketches_legacy(enc):
    from jax.experimental import multihost_utils
    gathered = np.asarray(multihost_utils.process_allgather(enc))
    return gathered
"""

WIRE_DTYPE_SUPPRESSED = """
import numpy as np

def gather_device_state(x):
    from jax.experimental import multihost_utils
    # device dtype already, tiled gather  # tpu-lint: disable=wire-dtype
    return np.asarray(multihost_utils.process_allgather(x, tiled=True))
"""

WIRE_DTYPE_BLESSED_CLEAN = """
import numpy as np

def _gather_np(x):
    import jax
    from jax.experimental import multihost_utils
    out = np.asarray(multihost_utils.process_allgather(x))
    return out.reshape((jax.process_count(),) + x.shape)
"""


def test_wire_dtype_seeded_f64_regression_fires():
    found = analyze_source(WIRE_DTYPE_FIRE, rules=["wire-dtype"])
    assert "wire-dtype" in names(found)
    assert any("wire_allgather" in f.message for f in found)


def test_wire_dtype_suppressed():
    assert "wire-dtype" not in names(analyze_source(
        WIRE_DTYPE_SUPPRESSED, rules=["wire-dtype"]))
    kept = analyze_source(WIRE_DTYPE_SUPPRESSED, rules=["wire-dtype"],
                          keep_suppressed=True)
    assert "wire-dtype" in names(kept)


def test_wire_dtype_blessed_codec_site_clean():
    # the codec's own gather primitive in parallel/multihost.py is the ONE
    # allowed raw call site...
    assert "wire-dtype" not in names(analyze_source(
        WIRE_DTYPE_BLESSED_CLEAN, rules=["wire-dtype"],
        relpath=MULTIHOST_REL))
    # ...and ONLY there: the same function anywhere else still fires
    assert "wire-dtype" in names(analyze_source(
        WIRE_DTYPE_BLESSED_CLEAN, rules=["wire-dtype"]))


NONADDR_FIRE = """
import numpy as np

def export_scores(score, plan, mh):
    if mh.plan_spans_processes(plan):
        return np.asarray(score, np.float32)
    return None
"""

NONADDR_SUPPRESSED = """
import numpy as np

def export_scores(score, plan, mh):
    if mh.plan_spans_processes(plan):
        # score is replicated  # tpu-lint: disable=nonaddressable-access
        return np.asarray(score, np.float32)
    return None
"""

NONADDR_GUARDED_CLEAN = """
import numpy as np

def export_scores(score, plan, mh):
    if mh.plan_spans_processes(plan):
        if not score.sharding.is_fully_addressable:
            score = mh.process_allgather(score, tiled=True)
        return np.asarray(score, np.float32)
    return None
"""

NONADDR_GATHER_FED_CLEAN = """
import numpy as np

def export_scores(score, plan, mh):
    if mh.plan_spans_processes(plan):
        # materializing a gather RESULT is host-local by construction, and
        # a materializer FEEDING a collective is this rank's contribution
        full = np.asarray(mh.process_allgather(score))
        mh.allgather_rows(np.asarray(score, np.float32), 10, 0)
        return full
    return None
"""

NONADDR_LITERAL_CLEAN = """
import numpy as np

def count_rows(n_local, plan, mh):
    if mh.plan_spans_processes(plan):
        return np.array([n_local], np.int64)
    return None
"""


def test_nonaddressable_access_fires():
    assert "nonaddressable-access" in names(analyze_source(
        NONADDR_FIRE, rules=["nonaddressable-access"]))


def test_nonaddressable_access_suppressed():
    assert "nonaddressable-access" not in names(analyze_source(
        NONADDR_SUPPRESSED, rules=["nonaddressable-access"]))
    kept = analyze_source(NONADDR_SUPPRESSED,
                          rules=["nonaddressable-access"],
                          keep_suppressed=True)
    assert "nonaddressable-access" in names(kept)


def test_nonaddressable_access_clean_variants():
    for src in (NONADDR_GUARDED_CLEAN, NONADDR_GATHER_FED_CLEAN,
                NONADDR_LITERAL_CLEAN):
        assert "nonaddressable-access" not in names(analyze_source(
            src, rules=["nonaddressable-access"])), src
