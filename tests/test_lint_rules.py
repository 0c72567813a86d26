"""Per-rule fixture tests for the flink_tpu.lint analyzer.

Every registered rule gets at least one violating and one clean fixture
snippet (ISSUE-5 acceptance criterion), synthesized as tiny packages in
tmp_path — the rules are package-relative by design, so the same code
paths run here and over the real flink_tpu tree. The trickier model
behaviors (helper-lock propagation, jax.jit(fn) resolution, deliberate
lock-order cycle, jit host-sync) get their own cases.
"""

import textwrap

import pytest

from flink_tpu.lint import ModuleIndex, all_rules, get_rule


def make_index(tmp_path, files, package="fixpkg"):
    """Materialize {relpath: source} as a package dir and index it."""
    root = tmp_path / package
    for rel, src in files.items():
        p = root / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    (root / "__init__.py").touch()
    return ModuleIndex(root)


def run_rule(rule_id, tmp_path, files, package="fixpkg"):
    index = make_index(tmp_path, files, package)
    return list(get_rule(rule_id).check(index))


def test_registry_has_at_least_eight_rules():
    rules = all_rules()
    assert len(rules) >= 8
    assert len({r.id for r in rules}) == len(rules)
    families = {r.family for r in rules}
    assert {"concurrency", "device", "wire"} <= families


# ---------------------------------------------------------------------------
# CONC001 inconsistent-guard
# ---------------------------------------------------------------------------

def test_conc001_flags_attribute_written_locked_and_bare(tmp_path):
    vs = run_rule("CONC001", tmp_path, {"w.py": """
        import threading

        class W:
            def __init__(self):
                self._lock = threading.Lock()
                self._count = 0

            def add(self):
                with self._lock:
                    self._count += 1

            def reset(self):
                self._count = 0
    """})
    assert len(vs) == 1
    assert vs[0].symbol == "_count"
    assert "inconsistent guard" in vs[0].message
    assert "reset" in vs[0].message


def test_conc001_clean_when_every_write_is_guarded(tmp_path):
    vs = run_rule("CONC001", tmp_path, {"w.py": """
        import threading

        class W:
            def __init__(self):
                self._lock = threading.Lock()
                self._count = 0          # construction write: exempt

            def add(self):
                with self._lock:
                    self._count += 1

            def get(self):
                with self._lock:
                    return self._count
    """})
    assert vs == []


def test_conc001_lock_held_helper_is_not_a_false_positive(tmp_path):
    """The Meter._trim pattern: a helper ONLY called under the lock
    inherits the callers' held set (one hop)."""
    vs = run_rule("CONC001", tmp_path, {"w.py": """
        import threading

        class Meter:
            def __init__(self):
                self._lock = threading.Lock()
                self._events = []

            def mark(self, n):
                with self._lock:
                    self._events.append(n)
                    self._trim()

            def rate(self):
                with self._lock:
                    self._trim()
                    return len(self._events)

            def _trim(self):
                self._events.pop()       # runs under callers' lock
    """})
    assert vs == []


def test_conc001_container_mutation_counts_as_write(tmp_path):
    vs = run_rule("CONC001", tmp_path, {"w.py": """
        import threading

        class W:
            def __init__(self):
                self._lock = threading.Lock()
                self._ring = []

            def push(self, x):
                with self._lock:
                    self._ring.append(x)

            def drop_all(self):
                self._ring.clear()
    """})
    assert [v.symbol for v in vs] == ["_ring"]


def test_conc001_module_level_container_mutation(tmp_path):
    """A module-global dict mutated in place needs no `global` statement —
    the bare .pop() must still count as an unguarded write."""
    vs = run_rule("CONC001", tmp_path, {"reg.py": """
        import threading

        _LOCK = threading.Lock()
        _CACHE = {}

        def put(k, v):
            with _LOCK:
                _CACHE[k] = v

        def drop(k):
            _CACHE.pop(k, None)
    """})
    assert len(vs) == 1
    assert vs[0].symbol == "_CACHE"
    assert "drop" in vs[0].message


# ---------------------------------------------------------------------------
# CONC002 lock-order-cycle
# ---------------------------------------------------------------------------

def test_conc002_flags_deliberate_lock_order_cycle(tmp_path):
    vs = run_rule("CONC002", tmp_path, {"w.py": """
        import threading

        class W:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()

            def ab(self):
                with self._a:
                    with self._b:
                        pass

            def ba(self):
                with self._b:
                    with self._a:
                        pass
    """})
    assert len(vs) == 1
    assert "lock-order cycle" in vs[0].message
    assert "_a" in vs[0].message and "_b" in vs[0].message


def test_conc002_clean_when_order_is_consistent(tmp_path):
    vs = run_rule("CONC002", tmp_path, {"w.py": """
        import threading

        class W:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()

            def one(self):
                with self._a:
                    with self._b:
                        pass

            def two(self):
                with self._a:
                    with self._b:
                        pass
    """})
    assert vs == []


def test_conc002_self_reacquire_of_plain_lock_is_deadlock(tmp_path):
    vs = run_rule("CONC002", tmp_path, {"w.py": """
        import threading

        class W:
            def __init__(self):
                self._lock = threading.Lock()

            def outer(self):
                with self._lock:
                    with self._lock:
                        pass
    """})
    assert len(vs) == 1
    assert "single-thread deadlock" in vs[0].message


def test_conc002_deadlock_through_lock_held_helper(tmp_path):
    """One-hop call-mediated edge: ab() holds _a and calls _grab_b()
    (which acquires _b), ba() nests the opposite way — a real a->b->a
    deadlock that pure lexical nesting misses."""
    vs = run_rule("CONC002", tmp_path, {"w.py": """
        import threading

        class W:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()

            def ab(self):
                with self._a:
                    self._grab_b()

            def _grab_b(self):
                with self._b:
                    pass

            def ba(self):
                with self._b:
                    with self._a:
                        pass
    """})
    assert len(vs) == 1
    assert "lock-order cycle" in vs[0].message


def test_conc002_self_deadlock_through_helper_call(tmp_path):
    """`with self._lock: self.close()` where close() re-acquires the same
    non-reentrant lock — single-thread deadlock via the call hop."""
    vs = run_rule("CONC002", tmp_path, {"w.py": """
        import threading

        class W:
            def __init__(self):
                self._lock = threading.Lock()

            def stop(self):
                with self._lock:
                    self.close()

            def close(self):
                with self._lock:
                    pass
    """})
    assert len(vs) == 1
    assert "single-thread deadlock" in vs[0].message


def test_conc002_rlock_reentry_is_legal(tmp_path):
    vs = run_rule("CONC002", tmp_path, {"w.py": """
        import threading

        class W:
            def __init__(self):
                self._lock = threading.RLock()

            def outer(self):
                with self._lock:
                    with self._lock:
                        pass
    """})
    assert vs == []


# ---------------------------------------------------------------------------
# CONC003 blocking-under-lock
# ---------------------------------------------------------------------------

def test_conc003_flags_sleep_under_lock(tmp_path):
    vs = run_rule("CONC003", tmp_path, {"w.py": """
        import threading
        import time

        class W:
            def __init__(self):
                self._lock = threading.Lock()

            def nap(self):
                with self._lock:
                    time.sleep(1.0)
    """})
    assert len(vs) == 1
    assert "time.sleep()" in vs[0].message


def test_conc003_clean_when_sleep_is_outside_the_region(tmp_path):
    vs = run_rule("CONC003", tmp_path, {"w.py": """
        import threading
        import time

        class W:
            def __init__(self):
                self._lock = threading.Lock()
                self._n = 0

            def loop(self):
                with self._lock:
                    n = self._n
                time.sleep(1.0)
                return n
    """})
    assert vs == []


def test_conc003_flags_blocking_socket_call_on_local_name(tmp_path):
    """`sock.accept()` / `conn.recv()` on a plain local variable — the
    most common spelling — must match, not just attribute-chain
    receivers."""
    vs = run_rule("CONC003", tmp_path, {"w.py": """
        import threading

        class W:
            def __init__(self):
                self._lock = threading.Lock()

            def serve(self, sock):
                with self._lock:
                    conn, addr = sock.accept()
                    return conn.recv(1024)
    """})
    assert len(vs) == 2
    assert any("sock.accept" in v.message for v in vs)
    assert any("conn.recv" in v.message for v in vs)


def test_conc003_distinct_fingerprints_per_site(tmp_path):
    """Two blocking calls in one scope must not collide on one
    fingerprint — otherwise one baseline entry suppresses both."""
    vs = run_rule("CONC003", tmp_path, {"w.py": """
        import threading
        import time

        class W:
            def __init__(self):
                self._lock = threading.Lock()

            def nap_twice(self):
                with self._lock:
                    time.sleep(0.1)
                    time.sleep(0.2)
    """})
    assert len(vs) == 2
    assert len({v.fingerprint for v in vs}) == 2


def test_conc003_propagates_into_lock_held_helper(tmp_path):
    vs = run_rule("CONC003", tmp_path, {"w.py": """
        import threading
        import time

        class W:
            def __init__(self):
                self._lock = threading.Lock()

            def outer(self):
                with self._lock:
                    self._slow()

            def _slow(self):
                time.sleep(0.5)
    """})
    assert len(vs) == 1
    assert vs[0].scope == "W._slow"


# ---------------------------------------------------------------------------
# CONC004 thread-hygiene
# ---------------------------------------------------------------------------

def test_conc004_flags_thread_without_daemon_and_name(tmp_path):
    vs = run_rule("CONC004", tmp_path, {"w.py": """
        import threading

        def spawn(fn):
            threading.Thread(target=fn).start()
    """})
    assert len(vs) == 1
    assert "daemon=" in vs[0].message and "name=" in vs[0].message


def test_conc004_flags_missing_name_only(tmp_path):
    vs = run_rule("CONC004", tmp_path, {"w.py": """
        import threading

        def spawn(fn):
            threading.Thread(target=fn, daemon=True).start()
    """})
    assert len(vs) == 1
    assert "name=" in vs[0].message and "daemon=" not in vs[0].message


def test_conc004_distinct_fingerprints_per_site(tmp_path):
    vs = run_rule("CONC004", tmp_path, {"w.py": """
        import threading

        def spawn_two(fn):
            threading.Thread(target=fn).start()
            threading.Thread(target=fn).start()
    """})
    assert len(vs) == 2
    assert len({v.fingerprint for v in vs}) == 2


def test_conc004_clean_with_both_kwargs(tmp_path):
    vs = run_rule("CONC004", tmp_path, {"w.py": """
        import threading

        def spawn(fn):
            threading.Thread(target=fn, daemon=True, name="fix-worker").start()
    """})
    assert vs == []


# ---------------------------------------------------------------------------
# CONC005 no-silent-swallow
# ---------------------------------------------------------------------------

_SWALLOW_SRC = """
    def ship(gw):
        try:
            gw.send()
        except Exception:
            pass

    def drain(gw):
        try:
            gw.recv()
        except:
            pass
        try:
            gw.ack()
        except (ValueError, Exception):
            pass
"""


def test_conc005_flags_broad_silent_swallows_in_runtime(tmp_path):
    vs = run_rule("CONC005", tmp_path, {"runtime/hb.py": _SWALLOW_SRC})
    assert len(vs) == 3
    assert {v.symbol for v in vs} == {"swallow@ship", "swallow@drain",
                                      "swallow@drain#2"}
    assert any("bare except" in v.message for v in vs)


def test_conc005_checkpoint_subtree_is_scoped_too(tmp_path):
    vs = run_rule("CONC005", tmp_path, {"checkpoint/st.py": _SWALLOW_SRC})
    assert len(vs) == 3


def test_conc005_clean_outside_the_scoped_subtrees(tmp_path):
    # the same swallows in api/ are not this rule's business
    vs = run_rule("CONC005", tmp_path, {"api/ds.py": _SWALLOW_SRC})
    assert vs == []


def test_conc005_clean_when_narrow_or_logged(tmp_path):
    vs = run_rule("CONC005", tmp_path, {"runtime/hb.py": """
        import logging

        def ship(gw):
            try:
                gw.send()
            except OSError:
                pass                    # narrow type: a per-fault decision

        def drain(gw, counters):
            try:
                gw.recv()
            except Exception as e:      # broad but COUNTED, not silent
                counters["missed"] += 1
            try:
                gw.ack()
            except Exception as e:
                logging.getLogger(__name__).debug("swallowed %r", e)
    """})
    assert vs == []


# ---------------------------------------------------------------------------
# DEV001 host-sync-in-jit
# ---------------------------------------------------------------------------

def test_dev001_flags_host_sync_in_decorated_jit(tmp_path):
    vs = run_rule("DEV001", tmp_path, {"k.py": """
        import jax
        import jax.numpy as jnp
        import numpy as np

        @jax.jit
        def step(x):
            y = jnp.cumsum(x)
            total = float(y[-1])
            arr = np.asarray(y)
            return total, arr
    """})
    labels = sorted(v.message for v in vs)
    assert len(vs) == 2
    assert any("float()" in m for m in labels)
    assert any("np.asarray()" in m for m in labels)


def test_dev001_resolves_jax_jit_of_local_function(tmp_path):
    vs = run_rule("DEV001", tmp_path, {"k.py": """
        import jax

        def build():
            def run(state, x):
                return state + x.item()
            return jax.jit(run)
    """})
    assert len(vs) == 1
    assert ".item()" in vs[0].message and "run()" in vs[0].message


def test_dev001_distinct_fingerprints_per_site(tmp_path):
    vs = run_rule("DEV001", tmp_path, {"k.py": """
        import jax

        @jax.jit
        def step(x, y):
            return x.item() + y.item()
    """})
    assert len(vs) == 2
    assert len({v.fingerprint for v in vs}) == 2


def test_dev001_clean_pure_jnp_body(tmp_path):
    vs = run_rule("DEV001", tmp_path, {"k.py": """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def step(x):
            n = int(x.shape[0])          # static metadata: fine
            return jnp.cumsum(x) / n

        def readback(y):
            return float(y[-1])          # outside jit: fine
    """})
    assert vs == []


# ---------------------------------------------------------------------------
# DEV002 jit-in-loop
# ---------------------------------------------------------------------------

def test_dev002_flags_jit_inside_loop_body(tmp_path):
    vs = run_rule("DEV002", tmp_path, {"k.py": """
        import jax

        def apply_all(xs):
            outs = []
            for x in xs:
                outs.append(jax.jit(lambda v: v * 2)(x))
            return outs
    """})
    assert len(vs) == 1
    assert "for loop" in vs[0].message


def test_dev002_clean_cached_builder(tmp_path):
    vs = run_rule("DEV002", tmp_path, {"k.py": """
        import functools
        import jax

        @functools.lru_cache(maxsize=None)
        def _build(n):
            return jax.jit(lambda v: v[:n])

        def apply_all(xs, n):
            fn = _build(n)
            return [fn(x) for x in xs]
    """})
    assert vs == []


def test_dev002_def_inside_loop_is_not_flagged(tmp_path):
    """A builder *defined* in a loop runs later — only direct jit calls in
    the loop body are per-iteration hazards."""
    vs = run_rule("DEV002", tmp_path, {"k.py": """
        import jax

        def make(fs):
            builders = []
            for f in fs:
                def build(f=f):
                    return jax.jit(f)
                builders.append(build)
            return builders
    """})
    assert vs == []


# ---------------------------------------------------------------------------
# DEV003 jax-free-control-plane
# ---------------------------------------------------------------------------

def test_dev003_flags_module_level_jax_in_control_plane(tmp_path):
    vs = run_rule("DEV003", tmp_path, {"runtime/rpc.py": """
        import jax

        def call():
            return jax.devices()
    """})
    assert len(vs) == 1
    assert "imports jax at module level" in vs[0].message


def test_dev003_lazy_jax_import_is_the_sanctioned_path(tmp_path):
    vs = run_rule("DEV003", tmp_path, {"runtime/rpc.py": """
        def device_path():
            import jax

            return jax.devices()
    """})
    assert vs == []


# ---------------------------------------------------------------------------
# WIRE001 no-bare-pickle
# ---------------------------------------------------------------------------

def test_wire001_flags_pickle_loads_on_network_plane(tmp_path):
    vs = run_rule("WIRE001", tmp_path, {"runtime/blob.py": """
        import pickle

        def decode(b):
            return pickle.loads(b)
    """})
    assert len(vs) == 1
    assert "pickle.loads" in vs[0].message


def test_wire001_flags_from_import_spelling(tmp_path):
    vs = run_rule("WIRE001", tmp_path, {"fs/store.py": """
        from pickle import loads

        def decode(b):
            return loads(b)
    """})
    assert len(vs) == 1
    assert "import loads" in vs[0].message


def test_wire001_fingerprint_is_line_independent(tmp_path):
    """Prepending unrelated code must not orphan a baseline entry (the
    symbol is occurrence-indexed, never line-numbered)."""
    files = {"runtime/blob.py": """
        import pickle

        def decode(b):
            return pickle.loads(b)
    """}
    fp1 = run_rule("WIRE001", tmp_path / "a", files)[0].fingerprint
    files2 = {"runtime/blob.py": """
        import os
        import pickle

        HEADER = os.sep

        def decode(b):
            return pickle.loads(b)
    """}
    fp2 = run_rule("WIRE001", tmp_path / "b", files2)[0].fingerprint
    assert fp1 == fp2


def test_wire001_clean_outside_network_planes_and_dumps_ok(tmp_path):
    vs = run_rule("WIRE001", tmp_path, {
        "security/framing.py": """
            import pickle

            def restricted_loads(b):
                return pickle.loads(b)   # the sanctioned implementation site
        """,
        "runtime/blob.py": """
            import pickle

            def encode(obj):
                return pickle.dumps(obj)   # serialization out is fine
        """,
    })
    assert vs == []


# ---------------------------------------------------------------------------
# WIRE002 serialization-free-dataplane
# ---------------------------------------------------------------------------

def test_wire002_flags_dumps_call_in_dataplane(tmp_path):
    vs = run_rule("WIRE002", tmp_path, {"runtime/dataplane.py": """
        from pickle import dumps

        def frame(batch):
            return dumps(batch)
    """})
    assert len(vs) == 2      # the from-import AND the call
    assert any("dumps(...)" in v.message for v in vs)


def test_wire002_clean_dataplane(tmp_path):
    vs = run_rule("WIRE002", tmp_path, {"runtime/dataplane.py": """
        def send(transport, sock, batch):
            return transport.send_data_frame(sock, batch)
    """})
    assert vs == []


# ---------------------------------------------------------------------------
# ARCH001 layer-dag
# ---------------------------------------------------------------------------

def test_arch001_flags_upward_module_level_import(tmp_path):
    vs = run_rule("ARCH001", tmp_path, {"core/thing.py": """
        import fixpkg.runtime.executor
    """})
    assert len(vs) == 1
    assert "layer 'core'" in vs[0].message


def test_arch001_from_package_import_module_spelling(tmp_path):
    """`from fixpkg import runtime` binds fixpkg.runtime — the ordinary
    spelling of the violation must not bypass the banned-prefix check."""
    vs = run_rule("ARCH001", tmp_path, {"core/thing.py": """
        from fixpkg import runtime
    """})
    assert len(vs) == 1
    assert "fixpkg.runtime" in vs[0].message


def test_arch001_lazy_import_is_the_escape_hatch(tmp_path):
    vs = run_rule("ARCH001", tmp_path, {"core/thing.py": """
        def execute():
            from fixpkg.runtime import executor

            return executor
    """})
    assert vs == []


def test_arch001_resolves_relative_imports(tmp_path):
    vs = run_rule("ARCH001", tmp_path, {"core/thing.py": """
        from ..runtime import executor
    """})
    assert len(vs) == 1


def test_arch001_relative_import_from_package_init(tmp_path):
    """In pkg/core/__init__.py the dotted module name IS the package, so
    `from ..runtime.executor import X` resolves one level differently than
    in a plain module — and an in-layer sibling import must stay clean."""
    vs = run_rule("ARCH001", tmp_path, {
        "core/__init__.py": """
            from ..runtime.executor import X
        """,
        "utils/__init__.py": """
            from .arrays import Y      # sibling within the layer: fine
        """,
        "utils/arrays.py": "Y = 1\n",
    })
    assert len(vs) == 1
    assert "core" in vs[0].message and "runtime" in vs[0].message


# ---------------------------------------------------------------------------
# ARCH002 checkpoint-below-runtime
# ---------------------------------------------------------------------------

def test_arch002_flags_even_lazy_runtime_imports(tmp_path):
    vs = run_rule("ARCH002", tmp_path, {"checkpoint/coordinator.py": """
        def restore():
            from fixpkg.runtime import executor

            return executor
    """})
    assert len(vs) == 1
    assert "lazy imports included" in vs[0].message


def test_arch002_clean_callback_flow(tmp_path):
    vs = run_rule("ARCH002", tmp_path, {"checkpoint/coordinator.py": """
        class Coordinator:
            def __init__(self, state_bytes_fn):
                self.state_bytes_fn = state_bytes_fn
    """})
    assert vs == []


# ---------------------------------------------------------------------------
# ARCH003 one-staging-path
# ---------------------------------------------------------------------------

_ONE_STAGE = """
    def stage(pipe, clock, steps):
        with dispatch_stage(clock, "stage.fill"):
            host = pipe.fill(steps)
        with task_io.dispatch_stage(clock, "stage.put"):
            return pipe.put(host)
"""


def test_arch003_flags_a_second_staging_site_and_a_prologue_branch(tmp_path):
    vs = run_rule("ARCH003", tmp_path, {
        "runtime/pipeline.py": _ONE_STAGE,
        "parallel/sharded.py": """
            def stage_on_mesh(pipe, clock, steps):
                with dispatch_stage(clock, "stage.put"):
                    return pipe.put(pipe.deal(steps))
        """,
        "runtime/fused_window_operator.py": """
            class Op:
                def _dispatch(self, group):
                    d = (self.pipe.run_records(group) if self.prologue
                         else self.pipe.run_key_ids(group))
                    return d
        """})
    assert sorted(v.symbol for v in vs) == [
        "pipe.run_key_ids", "pipe.run_records", "stage:stage.put"]
    second = next(v for v in vs if v.symbol == "stage:stage.put")
    # the first site in path order stays; the other is the violation
    assert second.path.endswith("runtime/pipeline.py")
    assert "parallel/sharded.py" in second.message


def test_arch003_clean_one_loop_and_other_prologue_branches(tmp_path):
    vs = run_rule("ARCH003", tmp_path, {
        "runtime/pipeline.py": _ONE_STAGE,
        "runtime/fused_window_operator.py": """
            class Op:
                def _dispatch(self, group):
                    with dispatch_stage(self.clock, "dispatch"):
                        return self.pipe.process_superbatch(group)

                def _emit(self, window, rows):
                    if self.prologue is not None:
                        return self._emit_dense_rows(window, rows)
                    return self._emit_keydict_rows(window, rows)
        """})
    assert vs == []


# ---------------------------------------------------------------------------
# DOC001 config-docs-complete
# ---------------------------------------------------------------------------

CONFIG_SRC = """
    class ConfigOptions:
        @staticmethod
        def key(k):
            return k


    OPT = ConfigOptions.key("lint.fixture.some-option")
"""


def test_doc001_flags_undocumented_option(tmp_path):
    vs = run_rule("DOC001", tmp_path, {"config.py": CONFIG_SRC})
    assert len(vs) == 1
    assert "lint.fixture.some-option" in vs[0].message


def test_doc001_clean_when_documented(tmp_path):
    (tmp_path / "docs").mkdir()
    (tmp_path / "docs" / "configuration.md").write_text(
        "| `lint.fixture.some-option` | ... |\n")
    vs = run_rule("DOC001", tmp_path, {"config.py": CONFIG_SRC})
    assert vs == []


# ---------------------------------------------------------------------------
# cross-rule sanity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rule_id",
                         sorted(r.id for r in all_rules()))
def test_every_rule_is_silent_on_an_empty_package(rule_id, tmp_path):
    vs = run_rule(rule_id, tmp_path, {"empty.py": "X = 1\n"})
    assert vs == []
