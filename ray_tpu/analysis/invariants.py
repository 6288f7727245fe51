"""Invariant-site checker family (I4xx) — the five AST lints that
grew up ad hoc in ``tests/test_concurrency_net.py`` (PR 1/2/3/6/8/9/10
satellites), re-homed as declarative site tables. Coverage is
preserved exactly: every package, file, method, and identifier the
test-file lints enforced is enforced here; the test file now just runs
this pass.

I401  weak spawn site — an ``ensure_future``/``create_task`` whose
      task object is discarded can be GC'd mid-await (r4's lost-reply
      bug class). Scans the asyncio-bearing runtime packages.
I402  missing transition event — every task/exchange/engine
      state-transition method must emit into its lifecycle stream
      (``self._event`` / ``self._task_event``), including methods that
      NO LONGER EXIST (a rename silently dropping its event is exactly
      the bug class).
I403  missing gauge refresh — every dispatch-queue / pipeline-window
      mutation site must refresh the telemetry high-water gauges.
I404  dropped trace context — every request-forwarding hop must carry
      the trace context or the waterfall breaks at that hop.
I405  missing step-accounting feed — every device-dispatch site must
      feed util/perfmodel's step accounting or the MFU/step series go
      stale and the roofline misattributes the step to host time.
I407  silent batch-inference / spill transition — every batch-inference
      operator state transition (data/llm.py lifecycle) and every
      object-store spill/restore site must emit an event; a silent
      transition means the operator trace or the cross-process spill
      ledger (``stats()`` counters, ``rtpu memory`` spill plane)
      quietly diverges from what actually happened.
I410  silent alert/incident transition — every alert-engine incident
      state change (open / resolve / refire) must append to the
      incident's event log; a silent transition means the on-call's
      timeline (``rtpu incident show``, the ``slo_breach`` ledger
      emission) quietly diverges from what the burn-rate evaluator
      actually decided.
I411  an import against the layers — ``ops <- models <- llm <-
      serve``: a lower package that imports a higher one (at any depth
      of nesting: inside a function the arrow is true only at call
      time, which is how it hid), or a module of ``models/`` that takes
      a sibling's underscore name (``from .laguna import _rmsnorm``,
      ``other._counters(...)``): what two families share lives in a shared
      module under a public name, so the next family reads an
      interface and not another family's privates.

Adding a new invariant lint = appending a row to the right table (or a
new table + ~10-line checker below). New site families go through this
module from now on, not through new ad-hoc test code.
"""

from __future__ import annotations

import ast
from typing import Iterable, Optional

from .core import Checker, Context, Finding, Module, register

# ---------------------------------------------------------------------------
# Reusable AST predicates (public: tests and future checkers use them)
# ---------------------------------------------------------------------------


def weak_spawn_sites(module: Module) -> list:
    """(line, src) of ensure_future/create_task calls whose task object
    is DISCARDED — not kept via _keep_task/spawn, assignment, await,
    return, or a container append/add."""

    def is_spawnish(call: ast.Call) -> bool:
        fn = call.func
        name = fn.attr if isinstance(fn, ast.Attribute) else getattr(
            fn, "id", "")
        return name in ("ensure_future", "create_task")

    def kept(call: ast.Call) -> bool:
        p = getattr(call, "_rt_parent", None)
        if isinstance(p, ast.Call):
            # Argument of another call: _keep_task(...), spawn-like
            # wrappers, list.append(...), set.add(...) all KEEP it.
            return True
        if isinstance(p, (ast.Assign, ast.AnnAssign, ast.AugAssign,
                          ast.Await, ast.Return, ast.NamedExpr)):
            return True
        if isinstance(p, ast.Attribute):
            # task = loop.create_task(...).<something> chains
            return True
        if isinstance(p, (ast.ListComp, ast.GeneratorExp, ast.List,
                          ast.Tuple, ast.comprehension)):
            return True
        return False

    return [(n.lineno, module.segment(n))
            for n in ast.walk(module.tree)
            if isinstance(n, ast.Call) and is_spawnish(n)
            and not kept(n)]


def methods_missing_call(module: Module, methods, callee: str) -> list:
    """Names from ``methods`` whose body never calls
    ``self.<callee>(...)`` — including methods that no longer exist
    (a rename silently dropping its emit is exactly the bug class)."""
    has_call: dict = {}
    for node in ast.walk(module.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                and node.name in methods:
            calls = {
                c.func.attr for c in ast.walk(node)
                if isinstance(c, ast.Call)
                and isinstance(c.func, ast.Attribute)
                and isinstance(c.func.value, ast.Name)
                and c.func.value.id == "self"}
            has_call[node.name] = (has_call.get(node.name, False)
                                   or callee in calls)
    return [m for m in methods if not has_call.get(m, False)]


def funcs_missing_name(module: Module, funcs, name: str) -> list:
    """Entries from ``funcs`` ("func" or "Class.method") whose body
    never references identifier ``name`` (bare name, attribute,
    parameter, or keyword argument) — including functions that no
    longer exist."""

    def refs(node) -> bool:
        for n in ast.walk(node):
            if isinstance(n, ast.Name) and n.id == name:
                return True
            if isinstance(n, ast.Attribute) and n.attr == name:
                return True
            if isinstance(n, ast.keyword) and n.arg == name:
                return True
            if isinstance(n, ast.arg) and n.arg == name:
                return True
        return False

    found: dict = {}
    for node in ast.walk(module.tree):
        if isinstance(node, ast.ClassDef):
            for ch in node.body:
                if isinstance(ch, (ast.FunctionDef,
                                   ast.AsyncFunctionDef)):
                    key = f"{node.name}.{ch.name}"
                    if key in funcs:
                        found[key] = found.get(key, False) or refs(ch)
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if node.name in funcs:
                found[node.name] = (found.get(node.name, False)
                                    or refs(node))
    return [f for f in funcs if not found.get(f, False)]


# ---------------------------------------------------------------------------
# Site tables (the declarative part — append here to extend coverage)
# ---------------------------------------------------------------------------

#: Packages whose asyncio spawn sites must keep a strong reference.
SPAWN_PACKAGES = ("ray_tpu/_private", "ray_tpu/serve", "ray_tpu/data",
                  "ray_tpu/util", "ray_tpu/llm")

#: (path, callee, (methods...), why) — every method must call
#: ``self.<callee>(...)``.
EVENT_SITE_TABLES = (
    ("ray_tpu/_private/node_service.py", "_event", (
        "submit",                 # SUBMITTED
        "_start_reconstruction",  # RECONSTRUCTING
        "_run_on_worker",    # RUNNING (cpu lane, head of a fresh lease)
        "_on_task_running",  # RUNNING (pipelined spec starts worker-side)
        "_requeue_unstarted",  # SUBMITTED (unstarted spec, dead worker)
        "_run_on_device",    # RUNNING + FINISHED (device lane)
        "_run_actor_task",   # RUNNING (actor call)
        "_handle_task_reply",  # FINISHED (cpu lane)
        "_fail_task",        # FAILED
        "_execute_remotely",  # FORWARDED
        "_handle_remote_reply",  # FINISHED/FAILED (owner side)
        "_actor_alive",      # FINISHED (actor creation)
    ), "task state-transition site emits no lifecycle event — the "
       "task_events stream (state API, timeline, phase metrics) "
       "silently loses that transition"),
    ("ray_tpu/_private/worker.py", "_task_event", (
        "_execute",          # ARGS_FETCHED + OUTPUT_SERIALIZED
    ), "worker-side task phase site emits no lifecycle event"),
    ("ray_tpu/data/exchange.py", "_event", (
        "_submit_map_round",    # MAP_ROUND_SUBMITTED
        "_submit_merge_round",  # MERGE_ROUND_SUBMITTED
        "_drain_round",         # ROUND_COMPLETED
        "_submit_reduce",       # REDUCE_SUBMITTED
        "_finish",              # FINISHED
    ), "exchange merge-round state change emits no event — "
       "list_exchanges/the dashboard pane silently lose it"),
    ("ray_tpu/llm/engine.py", "_event", (
        "add_request",  # WAITING
        "_admit",       # PREFILL (joined the in-flight batch)
        "_activate",    # RUNNING (prefill done, decoding)
        "_preempt",     # PREEMPTED (pool exhausted, blocks freed)
        "_finish",      # FINISHED (stop token / length / abort)
    ), "engine scheduler state-transition site emits no lifecycle "
       "event — the preempt+resume determinism tests and the request "
       "trace silently lose transitions"),
    ("ray_tpu/jobs/scheduler.py", "_event", (
        "submit",         # admitted / rejected (+ reason)
        "next_dispatch",  # dispatched (+ shape, cost, tenant pass)
        "on_finish",      # finished (+ outcome)
        "requeue",        # requeued (gang lost, back to head-of-line)
    ), "job-plane scheduling decision site emits no ledger event — "
       "fairness audits (ledger_shares, Jain index) and the rtpu jobs "
       "timeline silently lose that decision"),
    ("ray_tpu/job_submission.py", "_job_event", (
        "submit_job",  # queued
        "_finish",     # finished (+ return code)
        "stop_job",    # stopped
    ), "job lifecycle site emits no ledger event — the single "
       "scheduler/manager timeline silently loses the transition"),
    ("ray_tpu/autoscaler/instance_manager.py", "_record", (
        "request",          # instance requested
        "drain",            # drain requested
        "requeue_or_fail",  # requeue (backoff) or give_up (reasoned)
        "reconcile",        # FSM transitions
    ), "instance FSM decision site emits no record — scale-up/down "
       "forensics (why did this slice relaunch/fail?) go dark"),
    ("ray_tpu/autoscaler/autoscaler.py", "_event", (
        "update",  # launch / terminate decisions per pass
    ), "autoscaler decision site emits no event — the demand-driven "
       "launch/idle-terminate audit trail goes dark"),
)

#: Batch-inference operator lifecycle + object-store spill/restore
#: sites: every state transition / spill event must emit. The llm.py
#: rows cover the INIT/SUBMIT/DRAIN/EMIT/STOPPED lifecycle; the
#: object_store.py rows keep the cross-process ``.spill_log`` ledger
#: (and therefore ``stats()`` and the ``rtpu memory`` spill plane)
#: coherent with the files actually moved.
BATCH_SPILL_SITE_TABLES = (
    ("ray_tpu/data/llm.py", "_event", (
        "__init__",  # INIT (engine up, worker ready)
        "_submit",   # SUBMIT (block admitted, throughput-greedy burst)
        "_drain",    # DRAIN (blocking on engine completion)
        "apply",     # EMIT (output block built)
        "stop",      # STOPPED
    ), "batch-inference operator state transition emits no lifecycle "
       "event — the operator trace (stats()/events) silently loses "
       "the transition"),
    ("ray_tpu/_private/object_store.py", "_spill_event", (
        "_spill_one",  # S <bytes> (victim moved shm -> spill_dir)
        "_restore",    # R <bytes> (spill_dir -> shm on access)
    ), "spill/restore site bypasses the event ledger — the "
       "cross-process spill counters (stats(), telemetry series, "
       "rtpu memory) silently diverge from the bytes actually moved"),
)

#: Prefix-pool state changes that must land in the pool's event ring:
#: sharing (refcount bump on a cache hit), registration (new index
#: keys), COW splits and evictions are exactly the transitions the
#: cache-debugging story (prefix_stats(), kv_cache_hit_rate series)
#: is built on — a silent one makes hit/eviction telemetry lie.
PREFIX_POOL_SITE_TABLES = (
    ("ray_tpu/llm/kv_cache.py", "_event", (
        "admit",       # "share" (cache-hit blocks acquired, ref++)
        "register",    # "register" (new chunk keys indexed)
        "cow",         # "cow" (shared block split before divergent write)
        "_evict_one",  # "evict" (LRU parked block dropped for space)
    ), "prefix-pool state change emits no event — prefix_stats() and "
       "the kv_cache_hit_rate/kv_shared_blocks series silently diverge "
       "from what the allocator actually shared, split or evicted"),
)

#: Alert-engine incident state changes that must append to the
#: incident's event log: open/resolve/refire ARE the pager timeline —
#: a silent one and `rtpu incident show` (plus the slo_breach ledger
#: path those methods also drive) lies about when the rule fired.
ALERT_SITE_TABLES = (
    ("ray_tpu/_private/alerting.py", "_event", (
        "_open_incident",     # "open" (evidence snapshotted)
        "_resolve_incident",  # "resolve" (hysteresis hold satisfied)
        "_refire",            # "refire" (reopened within dedup window)
    ), "alert/incident state transition emits no event — the incident "
       "timeline and the slo_breach/slo_resolved ledger trail silently "
       "lose the transition the burn-rate evaluator made"),
)

#: Speculative-decode lifecycle sites that must land in the spec event
#: ring: PROPOSE/VERIFY/ACCEPT/ROLLBACK are exactly the transitions the
#: accept-rate story (SpecDecoder.stats(), llm_spec_accept_rate /
#: llm_spec_tokens_per_step series) is built on — a silent one makes
#: the speculation telemetry lie about what the verifier actually did.
SPEC_SITE_TABLES = (
    ("ray_tpu/llm/spec.py", "_event", (
        "propose",   # "propose" (draft tokens submitted for a lane)
        "verify",    # "verify" (lane entered the batched verify fwd)
        "accept",    # "accept" (accepted prefix + emitted count)
        "rollback",  # "rollback" (rejected slots freed via truncate)
    ), "speculative-decode transition emits no event — accept_rate/"
       "tokens_per_step and the llm_spec_* series silently diverge "
       "from what the verify step actually accepted or rolled back"),
)

#: Dispatch-queue / pipeline-window mutation sites that must refresh
#: the telemetry high-water gauges.
GAUGE_SITE_TABLES = (
    ("ray_tpu/_private/node_service.py", "_gauge_queues", (
        "_enqueue_local",      # pending_cpu.append (local submit)
        "_dispatch",           # pending_cpu = still_pending
        "_try_spill",          # pending_cpu.append (spill bounce-back)
        "_requeue_unstarted",  # pending_cpu re-queue off a dead worker
        "_retry_or_fail",      # pending_cpu.append (retry)
        "_handle_task_reply",  # pending_cpu.append (retry_exceptions)
        "_run_on_device",      # pending_cpu.append (device retry)
        "_handle_rpc",         # pending_cpu = keep (register setup_err)
        "_acquire_worker",     # inflight[...] = spec (pipelined lease)
        "_run_on_worker",      # inflight[...] = spec (fresh lease)
        "_run_actor_task",     # inflight[...] = spec (actor lane)
    ), "dispatch-queue/pipeline-window mutation site never refreshes "
       "the telemetry gauges — dispatch_queue_hw/pipeline_inflight_hw "
       "miss between-sample bursts"),
)

#: (path, identifier, (funcs...), why) — every func must reference the
#: identifier.
REF_SITE_TABLES = (
    ("ray_tpu/serve/http_proxy.py", "copy_context", (
        "HTTPProxy._handle_routed",
    ), "the proxy's executor handoff drops contextvars — trace context "
       "does not cross run_in_executor without copy_context"),
    ("ray_tpu/serve/deployment.py", "trace_ctx", (
        "DeploymentHandle.remote", "DeploymentResponse.result",
    ), "request-forwarding hop drops the trace context — the "
       "waterfall breaks at that hop"),
    ("ray_tpu/serve/replica.py", "trace_ctx", (
        "Replica.handle_request",
    ), "request-forwarding hop drops the trace context"),
    ("ray_tpu/serve/batching.py", "trace_ctx", (
        "_Pending.__init__", "_Batcher._run_batch",
    ), "request-forwarding hop drops the trace context"),
    ("ray_tpu/llm/engine.py", "trace_ctx", (
        "LLMEngine.add_request",
    ), "request-forwarding hop drops the trace context"),
    ("ray_tpu/serve/llm.py", "trace_ctx", (
        "_LLMServer.__call__",
    ), "request-forwarding hop drops the trace context"),
)

#: Device-dispatch sites that must feed perfmodel's step accounting.
PERF_SITE_TABLES = (
    ("ray_tpu/llm/engine.py", "_step_perf", (
        "LLMEngine._run_prefills", "LLMEngine._run_decode",
        "LLMEngine.step", "LLMEngine._publish_gauges",
    ), "device-dispatch site bypasses the step accounting — the "
       "MFU/step-breakdown series go stale or misattribute the step "
       "to host time"),
    ("ray_tpu/train/session.py", "_drain_step_perf", (
        "_TrainSession.report",
    ), "train report() does not drain the accumulated device spans"),
    ("ray_tpu/train/session.py", "record_device", (
        "wrap_step",
    ), "the public wrap_step does not feed the step accounting"),
)

#: Eager-collective entry/exit sites that must feed the gang flight
#: recorder (parallel/flightrec.record_op). The module-level
#: allreduce/broadcast/barrier wrappers delegate to these methods, so
#: the group methods are the complete set of recording sites; in-graph
#: collectives compile into XLA and are covered at step granularity by
#: wrap_step's record_op (also listed here).
FLIGHTREC_SITE_TABLES = (
    ("ray_tpu/parallel/collectives.py", "record_op", (
        "CollectiveGroup.allreduce", "CollectiveGroup.broadcast",
        "CollectiveGroup.allgather", "CollectiveGroup.reducescatter",
        "CollectiveGroup.barrier",
    ), "eager collective site bypasses the flight recorder — the ring "
       "gaps here, and a gang desync at this op is undiagnosable "
       "(`rtpu gang doctor` would name the wrong op or nothing)"),
    ("ray_tpu/train/session.py", "record_op", (
        "wrap_step",
    ), "the compiled-step boundary is not recorded — in-graph "
       "collectives lose their only (step-granularity) ring coverage"),
)


#: The packages' layers, ``ops <- models <- llm <- serve``: (a
#: package's path, the packages no module under it may import, why).
LAYER_TABLES = (
    ("ray_tpu/ops", ("ray_tpu.models", "ray_tpu.llm", "ray_tpu.serve"),
     "a kernel or an op knows no model, engine or deployment"),
    ("ray_tpu/models", ("ray_tpu.llm", "ray_tpu.serve"),
     "a model family is below the engine that serves it: what both "
     "read of a pool's layout lives in models/seam.py"),
    ("ray_tpu/llm", ("ray_tpu.serve",),
     "the generation engine knows no deployment"),
)
#: Packages whose modules take nothing of one another by an underscore
#: name: a shared part has a public name in a shared module.
PRIVATE_IMPORT_PACKAGES = ("ray_tpu/models",)


def imports(module: Module):
    """(node, absolute dotted module, imported names) of every import
    statement of a module, at any depth of nesting; a relative import
    resolved against the module's package."""
    package = module.relpath.split("/")[:-1]
    for node in ast.walk(module.tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                yield node, alias.name, ()
        elif isinstance(node, ast.ImportFrom):
            base = package[:len(package) - (node.level - 1)] \
                if node.level else []
            full = ".".join(base + ([node.module] if node.module else []))
            yield node, full, tuple(node.names)


def _within(dotted: str, package: str) -> bool:
    return dotted == package or dotted.startswith(package + ".")


# ---------------------------------------------------------------------------
# Checkers
# ---------------------------------------------------------------------------
@register
class WeakSpawnSite(Checker):
    id = "I401"
    family = "invariants"
    severity = "P0"
    scope = "repo"

    def check_repo(self, ctx: Context) -> Iterable[Finding]:
        pkgs = ctx.config.get("spawn_packages", SPAWN_PACKAGES)
        for module in ctx.modules:
            if not any(module.relpath.startswith(p + "/")
                       or module.relpath == p for p in pkgs):
                continue
            for line, src in weak_spawn_sites(module):
                yield Finding(
                    checker=self.id, family=self.family, severity="P0",
                    path=module.relpath, line=line, col=0,
                    symbol="", snippet=src,
                    message=("fire-and-forget task with no strong "
                             "reference — asyncio may GC it mid-await "
                             "(wrap in _keep_task()/spawn())"))


class _TableChecker(Checker):
    """Shared driver for the site-table checkers: report every table
    entry whose method/function is missing its required call/ref —
    including entries whose file is gone entirely."""

    scope = "repo"
    tables: tuple = ()
    mode = "method_call"   # or "name_ref"

    def check_repo(self, ctx: Context) -> Iterable[Finding]:
        tables = ctx.config.get(f"{self.id}_tables", self.tables)
        for path, needle, entries, why in tables:
            module = ctx.by_relpath.get(path)
            if module is None:
                yield Finding(
                    checker=self.id, family=self.family, severity="P0",
                    path=path, line=1, col=0, symbol="",
                    message=(f"file named by an invariant site table "
                             f"is missing — {why}"),
                    snippet=f"expected: {path}")
                continue
            if self.mode == "method_call":
                missing = methods_missing_call(module, entries, needle)
            else:
                missing = funcs_missing_name(module, entries, needle)
            for m in missing:
                yield Finding(
                    checker=self.id, family=self.family, severity="P0",
                    path=path, line=_site_line(module, m), col=0,
                    symbol=m, snippet=f"required: {needle}",
                    message=f"{m}: {why}")


def _site_line(module: Module, entry: str) -> int:
    name = entry.rsplit(".", 1)[-1]
    for node in ast.walk(module.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                and node.name == name:
            return node.lineno
    return 1


@register
class MissingTransitionEvent(_TableChecker):
    id = "I402"
    family = "invariants"
    severity = "P0"
    tables = EVENT_SITE_TABLES
    mode = "method_call"


@register
class MissingGaugeRefresh(_TableChecker):
    id = "I403"
    family = "invariants"
    severity = "P0"
    tables = GAUGE_SITE_TABLES
    mode = "method_call"


@register
class DroppedTraceContext(_TableChecker):
    id = "I404"
    family = "invariants"
    severity = "P0"
    tables = REF_SITE_TABLES
    mode = "name_ref"


@register
class MissingStepAccounting(_TableChecker):
    id = "I405"
    family = "invariants"
    severity = "P0"
    tables = PERF_SITE_TABLES
    mode = "name_ref"


@register
class MissingFlightRecord(_TableChecker):
    id = "I406"
    family = "invariants"
    severity = "P0"
    tables = FLIGHTREC_SITE_TABLES
    mode = "name_ref"


@register
class SilentBatchSpillTransition(_TableChecker):
    id = "I407"
    family = "invariants"
    severity = "P0"
    tables = BATCH_SPILL_SITE_TABLES
    mode = "method_call"


@register
class SilentPrefixPoolTransition(_TableChecker):
    id = "I408"
    family = "invariants"
    severity = "P0"
    tables = PREFIX_POOL_SITE_TABLES
    mode = "method_call"


@register
class SilentSpecTransition(_TableChecker):
    id = "I409"
    family = "invariants"
    severity = "P0"
    tables = SPEC_SITE_TABLES
    mode = "method_call"


@register
class SilentAlertTransition(_TableChecker):
    id = "I410"
    family = "invariants"
    severity = "P0"
    tables = ALERT_SITE_TABLES
    mode = "method_call"


@register
class ImportAgainstTheLayers(Checker):
    id = "I411"
    family = "invariants"
    severity = "P0"
    scope = "repo"

    def _finding(self, module, node, message):
        return Finding(
            checker=self.id, family=self.family, severity="P0",
            path=module.relpath, line=node.lineno, col=node.col_offset,
            symbol="", snippet=module.segment(node), message=message)

    def check_repo(self, ctx: Context) -> Iterable[Finding]:
        layers = ctx.config.get("I411_tables", LAYER_TABLES)
        private = ctx.config.get("I411_private", PRIVATE_IMPORT_PACKAGES)
        under = lambda module, path: module.relpath.startswith(path + "/")
        for module in ctx.modules:
            banned = [(path, b, why) for path, names, why in layers
                      if under(module, path) for b in names]
            package = next((p for p in private if under(module, p)), None)
            if not banned and package is None:
                continue
            found = list(imports(module))
            for node, full, names in found:
                # ``from .. import llm`` names the package too.
                reached = [full] + [f"{full}.{a.name}" for a in names]
                hit = next((row for row in banned
                            if any(_within(r, row[1]) for r in reached)),
                           None)
                if hit:
                    yield self._finding(
                        module, node, f"{hit[0]}/ imports {hit[1]}: {hit[2]}")
            if package is None:
                continue
            dotted = package.replace("/", ".")
            is_private = lambda name: name.startswith("_") \
                and not name.startswith("__")
            why = (f"a sibling's private name; what two modules of "
                   f"{package}/ share has a public name in a shared module")
            siblings = set()        # names bound to modules of the package
            for node, full, names in found:
                if not _within(full, dotted):
                    continue
                for a in names:
                    if full == dotted:      # ``from . import kimi_k2``
                        siblings.add(a.asname or a.name)
                    elif is_private(a.name):
                        yield self._finding(
                            module, node, f"takes {a.name} from {full}: {why}")
            for node in ast.walk(module.tree):
                if isinstance(node, ast.Attribute) \
                        and isinstance(node.value, ast.Name) \
                        and node.value.id in siblings \
                        and is_private(node.attr):
                    yield self._finding(
                        module, node,
                        f"reaches {node.value.id}.{node.attr}: {why}")
