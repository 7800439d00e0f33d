"""Single-host runner: optimize → translate → stream execute.

Reference parity: daft/runners/native_runner.py:64 (NativeRunner.run/run_iter).
"""

from __future__ import annotations

from typing import Iterator, List

from ..core.micropartition import MicroPartition
from ..plan.builder import LogicalPlanBuilder


class Runner:
    def run(self, builder: LogicalPlanBuilder) -> List[MicroPartition]:
        return list(self.run_iter(builder))

    def run_iter(self, builder: LogicalPlanBuilder) -> Iterator[MicroPartition]:
        raise NotImplementedError


class NativeRunner(Runner):
    def run_iter(self, builder: LogicalPlanBuilder) -> Iterator[MicroPartition]:
        """The query's stream. While a SpanRecorder is installed it is the
        `query` span, the root of the query's span tree: from the first pull
        to the stream's end, with rows out and the error it died of."""
        from ..observability.runtime_stats import current_spans, span_iter

        stream = self._run_iter(builder)
        if current_spans() is None:
            return stream
        import uuid

        return span_iter("query", "query", stream, qid=uuid.uuid4().hex[:12])

    def _run_iter(self, builder: LogicalPlanBuilder) -> Iterator[MicroPartition]:
        from ..observability.runtime_stats import (StatsCollector, current_qid,
                                                   profile_span, set_collector,
                                                   timed_span)

        # `query.open`, `query.plan_key`, `query.close`: the runner's own
        # bookkeeping around the plan and the stream, which was the `query`
        # root's unnamed self time (they lie inside the root: this body runs
        # during the root's pulls, its `finally` during the last one or the
        # root's own close)
        with profile_span("query.open", "query") as sp:
            import time
            import uuid

            from ..execution.executor import execute_plan
            from ..observability import (QueryEnd, QueryOptimized, QueryStart,
                                         flight, notify, subscribers_active)
            from ..plan.physical import translate

            observed = subscribers_active()
            # the flight recorder records EVERY query (bounded ring, anomaly
            # triggers), not just subscriber-observed ones; None when disabled
            frec = flight.recorder()
            # one id for the query's events and its span tree
            qid = current_qid() or (
                uuid.uuid4().hex[:12] if (observed or frec is not None) else "")
            t_start = time.perf_counter()
            reg_before = {}
            if observed or frec is not None:
                from ..observability.metrics import registry

                # per-query engine-path attribution (device batches, shuffle
                # bytes): counter deltas land in QueryEnd.metrics and the
                # flight ring's query record
                reg_before = registry().snapshot()
            if observed:
                notify("on_query_start", QueryStart(qid, builder.plan.display()))
            if sp is not None:
                sp.args["observed"], sp.args["recorded"] = observed, frec is not None
        # QueryOptimized carries the plan.* extents: taken either way when a
        # subscriber listens, only while a recorder is installed otherwise
        span = timed_span if observed else profile_span
        with span("plan.optimize", "plan") as sp_opt:
            optimized = builder.optimize()
        with span("plan.translate", "plan") as sp_tr:
            phys = translate(optimized.plan)
        with profile_span("query.plan_key", "query"):
            fkey = flight.plan_key(phys.display()) if frec is not None else ""
            if observed:
                notify("on_query_optimized", QueryOptimized(
                    qid, optimized.plan.display(), phys.display(),
                    sp_opt.seconds + sp_tr.seconds))
        from ..observability import placement
        from ..observability.runtime_stats import current_collector

        # inherit any ambient collector (explain_analyze routes through the
        # runner — it wins even with subscribers attached, who then see the
        # same collector's stats); save/restore around every pull so
        # interleaved queries on one thread never clobber each other's stats
        prev = current_collector()
        collector = prev if prev is not None \
            else (StatsCollector() if observed else None)
        # placement scope, same inheritance/save-restore discipline: an
        # ambient scope (explain_placement) wins; otherwise an observed query
        # gets its own so QueryEnd carries the decisions; unobserved queries
        # run scope-less (the zero-overhead path)
        prev_scope = placement.current_scope()
        pscope = prev_scope if prev_scope is not None \
            else (placement.PlacementScope() if observed else None)
        rows = 0
        err: str = None
        try:
            set_collector(collector)
            placement.set_scope(pscope)
            try:
                stream = execute_plan(phys)
            finally:
                set_collector(prev)
                placement.set_scope(prev_scope)
            while True:
                set_collector(collector)
                placement.set_scope(pscope)
                try:
                    part = next(stream)
                except StopIteration:
                    break
                finally:
                    set_collector(prev)
                    placement.set_scope(prev_scope)
                rows += part.num_rows
                yield part
        except Exception as e:
            err = f"{type(e).__name__}: {e}"
            raise
        finally:
            set_collector(prev)
            placement.set_scope(prev_scope)
            with profile_span("query.close", "query", rows=rows):
                seconds = time.perf_counter() - t_start
                from ..observability.metrics import registry

                # every query's wall time, the one counter a warm query moves:
                # what set-up spent inside queries is then a sum a reader can
                # take, and the cold counters' seconds have a whole to be part of
                registry().inc("query_wall_us", int(seconds * 1e6))
                deltas = {}
                if observed or frec is not None:
                    deltas = registry().diff(reg_before)
                placements = pscope.to_dicts() if pscope is not None else []
                if observed:
                    stats = collector.finish() if collector else []
                    for s in stats:
                        notify("on_operator_stats", qid, s)
                    notify("on_query_end", QueryEnd(
                        qid, rows, seconds, err, stats,
                        metrics=deltas, placements=placements))
                if frec is not None:
                    # always-on black box: the query record + the slow-query /
                    # query-error anomaly checks (observability/flight.py)
                    frec.note_query(fkey, seconds, query_id=qid, rows=rows,
                                    error=err, metrics=deltas,
                                    placements=placements or None)
