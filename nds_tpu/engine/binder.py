"""Binder/analyzer: SQL AST -> logical plan.

Responsibilities: name resolution over nested scopes, `*` expansion, CTE
registration (shared-identity plans so multiply-referenced CTEs materialize
once), predicate classification (pushdown / equi-join edges / residual),
subquery transformation (uncorrelated scalar -> cached broadcast; IN/EXISTS ->
semi/anti join; correlated scalar -> group-aggregate + left join, the standard
decorrelation for TPC-DS q1-style subqueries), aggregate/window extraction and
post-aggregation expression rewriting, ROLLUP grouping sets.

Counterpart of Spark Catalyst's analyzer, which the reference relies on via
`spark.sql(...)` (reference: nds/nds_power.py:125-135).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

from . import expr as E
from . import plan as P
from .sql import ast as A


class BindError(Exception):
    pass


class Relation:
    """A FROM-item bound to a plan: output columns are qualified names."""

    def __init__(self, plan, alias, columns):
        self.plan = plan
        self.alias = alias  # may be None for joined compounds
        self.columns = columns  # list of (qualified_name, bare_name, rel_alias)

    def find(self, name, qualifier=None):
        out = []
        for qn, bare, ra in self.columns:
            if bare == name and (qualifier is None or ra == qualifier):
                out.append(qn)
        return out


class Scope:
    def __init__(self, relations, parent=None, aliases=None):
        self.relations = relations  # list[Relation]
        self.parent = parent
        self.aliases = aliases or {}  # select-item alias -> Expr

    def resolve(self, name, qualifier=None):
        """Returns (qualified_name, is_outer)."""
        hits = []
        for r in self.relations:
            hits += r.find(name, qualifier)
        if len(hits) == 1:
            return hits[0], False
        if len(hits) > 1:
            # same qualified name reachable through several compound relations
            if all(h == hits[0] for h in hits):
                return hits[0], False
            raise BindError(f"ambiguous column {qualifier+'.' if qualifier else ''}{name}: {hits}")
        if self.parent is not None:
            qn, _ = self.parent.resolve(name, qualifier)
            return qn, True
        raise BindError(f"cannot resolve column {qualifier+'.' if qualifier else ''}{name}")


class Binder:
    def __init__(self, catalog):
        self.catalog = catalog  # object with .schema(name) -> Schema | None
        self._counter = 0
        self._cte_plans = {}  # name -> (plan, columns) registered per bind
        self._subquery_residual = None  # set by _CorrelatedBinder.run
        # evidence log of LEFT->INNER promotions this bind performed:
        # {"conjunct": raw AST conjunct, "refs": promoted-side columns}.
        # The plan verifier (analysis/verifier.py) re-derives the
        # null-rejecting shape of each recorded conjunct — a promotion
        # from a null-tolerant predicate silently drops the outer join's
        # null-extended rows (the PR-1 wrong-LEFT->INNER bug class).
        self.promotions = []

    def fresh(self, prefix="_c"):
        self._counter += 1
        return f"{prefix}{self._counter}"

    # ------------------------------------------------------------------
    def bind(self, stmt: A.SelectStmt) -> P.PlanNode:
        plan, _cols = self.bind_select(stmt, None, {})
        return plan

    # ------------------------------------------------------------------
    def bind_select(self, stmt: A.SelectStmt, outer: Optional[Scope], views):
        """Returns (plan, out_columns [(out_name, alias)])."""
        views = dict(views)
        for name, sub in stmt.ctes:
            sub_plan, sub_cols = self.bind_select(sub, None, views)
            views[name.lower()] = (sub_plan, sub_cols)

        plan, cols = self._bind_core(stmt, outer, views)

        for op, rhs in stmt.set_ops:
            rplan, rcols = (
                self.bind_select(rhs, outer, views)
                if (rhs.ctes or rhs.set_ops)
                else self._bind_core(rhs, outer, views)
            )
            if len(rcols) != len(cols):
                raise BindError("set operation column count mismatch")
            # align rhs output names to lhs
            rplan = P.Project(
                [(E.Col(rn), ln) for (rn, _), (ln, _) in zip(rcols, cols)], rplan
            )
            kind = {"union all": "union_all", "union": "union",
                    "intersect": "intersect", "except": "except"}[op]
            plan = P.SetOp(kind, plan, rplan)

        if stmt.set_ops and (stmt.order_by or stmt.limit is not None):
            # outer ORDER BY binds to the unioned output columns
            out_aliases = {a: E.Col(n) for n, a in cols if a}
            for n, a in cols:
                out_aliases.setdefault(n, E.Col(n))
            if stmt.order_by:
                skeys = []
                for it in stmt.order_by:
                    e = it.expr
                    if isinstance(e, E.Lit) and isinstance(e.value, int):
                        e = E.Col(cols[e.value - 1][0])
                    elif isinstance(e, E.Col) and e.table is None and e.name in out_aliases:
                        e = out_aliases[e.name]
                    else:
                        e = self._bind_expr(e, Scope([Relation(None, None, [(n, a or n, None) for n, a in cols])]), views)
                    skeys.append((e, it.ascending, it.nulls_first))
                plan = P.Sort(skeys, plan)
            if stmt.limit is not None:
                plan = P.Limit(stmt.limit, plan)
        return plan, cols

    # ------------------------------------------------------------------
    def _bind_core(self, stmt: A.SelectStmt, outer, views):
        # 1. FROM — flatten explicit INNER JOIN ... ON chains into the same
        # relation list + conjunct pool as comma-FROM/WHERE queries, so ON
        # equalities become MultiJoin edges the greedy order optimizer can
        # reorder and merge into multi-key joins. Left-deep binary execution
        # of the q72 shape (catalog_sales JOIN inventory ON item_sk alone,
        # week/date constraints arriving only via later date_dim joins)
        # otherwise materializes a ~1e9-pair candidate table. LEFT JOINs are
        # held back as pending joins applied after the inner core (they
        # commute with inner joins on preserved-side columns); other kinds
        # (right/full/semi/anti) stay opaque binary trees.
        relations = []
        # (conjunct AST, visible relation range [lo, hi)): an ON clause
        # sees exactly its join's operands — the relations flattened under
        # that JoinClause — not sibling FROM items, so each conjunct
        # remembers its operand range and is later bound in that narrowed
        # scope (a bare column ambiguous against a sibling's column, or a
        # forward reference, must behave as it did under binary binding)
        on_conjuncts = []
        pending_left = []  # (relation index, raw ON AST, visible-from idx)
        outer_idx = set()  # relation indices held out of the MultiJoin

        def flatten(item):
            if isinstance(item, A.JoinClause) and item.kind in (
                "inner", "cross",
            ):
                lo = len(relations)
                flatten(item.left)
                flatten(item.right)
                if item.on is not None:
                    hi = len(relations)
                    on_conjuncts.extend(
                        (c, lo, hi) for c in _conjuncts(item.on)
                    )
                return
            if isinstance(item, A.JoinClause) and item.kind == "left":
                lo = len(relations)
                flatten(item.left)
                r = self._bind_from_item(item.right, outer, views)
                relations.append(r)
                outer_idx.add(len(relations) - 1)
                pending_left.append((len(relations) - 1, item.on, lo))
                return
            relations.append(self._bind_from_item(item, outer, views))

        if stmt.from_items:
            for item in stmt.from_items:
                flatten(item)
        else:
            # FROM-less SELECT: single-row dummy relation
            relations.append(Relation(P.MaterializedScan("__dual__"), "__dual__", []))
        scope = Scope(relations, outer)

        # 2. WHERE + flattened-ON classification
        filters_per_rel = {i: [] for i in range(len(relations))}
        edges = []
        residual = []
        post_join_subqueries = []  # (kind, ...) applied after MultiJoin
        # pool entries are (conjunct, binding scope): ON conjuncts bind in
        # their join's operand range, WHERE conjuncts in the full scope
        raw_conjuncts = []
        for c, lo, hi in on_conjuncts:
            cscope = Scope(relations[lo:hi], outer)
            # factor conjuncts common to every OR branch so join keys
            # buried in disjunctions (TPC-DS q13/q48 shape) become edges
            # instead of forcing a cross join
            raw_conjuncts.extend((f, cscope) for f in _factor_or(c))
        if stmt.where is not None:
            for raw in _conjuncts(stmt.where):
                raw_conjuncts.extend((f, scope) for f in _factor_or(raw))

        # Null-rejection promotion: a strict comparison in the conjunct pool
        # that references a pending LEFT JOIN's right side filters out that
        # join's null-extended rows, so the join is semantically INNER.
        # Promote it into the MultiJoin core — otherwise its equalities are
        # unusable as edges and the core disconnects into a cross join
        # (TPC-DS q93: `ss LEFT JOIN sr ON ..., reason WHERE
        # sr_reason_sk = r_reason_sk` would execute store_sales x reason).
        rel_cols = [{qn for qn, _, _ in r.columns} for r in relations]
        work = list(raw_conjuncts)
        while work and outer_idx:
            conj, cscope = work.pop()
            if not _null_rejecting_shape(conj):
                continue
            try:
                refs = _refs(self._bind_expr(conj, cscope, views))
            except BindError:
                continue
            for idx in sorted(outer_idx):
                if refs & rel_cols[idx]:
                    outer_idx.discard(idx)
                    self.promotions.append({
                        "conjunct": conj,
                        "refs": sorted(refs & rel_cols[idx]),
                    })
                    for pi, (pidx, on_ast, plo) in enumerate(pending_left):
                        if pidx == idx:
                            pending_left.pop(pi)
                            if on_ast is not None:
                                pscope = Scope(
                                    relations[plo:pidx + 1], outer
                                )
                                newc = [
                                    (c, pscope)
                                    for r_ in _conjuncts(on_ast)
                                    for c in _factor_or(r_)
                                ]
                                raw_conjuncts.extend(newc)
                                work.extend(newc)
                            break

        for conj, cscope in raw_conjuncts:
            self._classify_conjunct(
                conj, cscope, relations, views,
                filters_per_rel, edges, residual, post_join_subqueries,
                joinable=set(range(len(relations))) - outer_idx,
            )

        # 3. assemble join tree: inner MultiJoin core, then pending LEFT
        # joins in FROM order, then the residual filter (WHERE applies
        # after all FROM joins)
        inner_order = [i for i in range(len(relations)) if i not in outer_idx]
        remap = {i: pos for pos, i in enumerate(inner_order)}
        rel_plans = []
        for i in inner_order:
            p = relations[i].plan
            preds = filters_per_rel[i]
            if preds:
                p = P.Filter(_conjoin(preds), p)
            rel_plans.append(p)
        if len(rel_plans) == 1 and not edges:
            base = rel_plans[0]
        else:
            base = P.MultiJoin(
                rel_plans,
                [(remap[i], remap[j], le, re_) for (i, j, le, re_) in edges],
                None,
            )
        applied_cols = set()
        for i in inner_order:
            applied_cols |= rel_cols[i]
        for idx, on_ast, plo in pending_left:
            r = relations[idx]
            rcols = rel_cols[idx]
            lkeys, rkeys, jres = [], [], []
            if on_ast is not None:
                cond = self._bind_expr(
                    on_ast, Scope(relations[plo:idx + 1], outer), views
                )
                lkeys, rkeys, jres = _split_equi_conjuncts(
                    _conjuncts(cond), applied_cols, rcols
                )
            base = P.Join(
                "left", base, r.plan, lkeys, rkeys, _conjoin_ast(jres)
            )
            applied_cols |= rcols
        if residual:
            base = P.Filter(_conjoin(residual), base)
        # semi/anti/scalar-correlated joins after the main join
        for entry in post_join_subqueries:
            base = entry(base)

        # 4. select items: expand *, name them
        items = []  # (raw Expr (bound), out_name, alias_for_user)
        for sexpr, alias in stmt.select_items:
            if sexpr == "*":
                qual = alias  # ('*', qualifier) packs qualifier in alias slot
                for r in relations:
                    for qn, bare, ra in r.columns:
                        if qual is None or ra == qual:
                            items.append((E.Col(qn), bare))
            else:
                bound = self._bind_expr(sexpr, scope, views)
                items.append((bound, alias))
        named_items = []
        for bound, alias in items:
            if alias is None:
                if isinstance(bound, E.Col):
                    alias = bound.name.split(".")[-1]
                else:
                    alias = self.fresh("_c")
            named_items.append((bound, alias))
        scope.aliases = {a: e for e, a in named_items}

        having = (
            self._bind_expr(stmt.having, scope, views)
            if stmt.having is not None
            else None
        )
        order_exprs = []
        for it in stmt.order_by:
            e = it.expr
            if isinstance(e, E.Lit) and isinstance(e.value, int):
                e = named_items[e.value - 1][0]
            elif (
                isinstance(e, E.Col)
                and e.table is None
                and e.name in scope.aliases
            ):
                e = scope.aliases[e.name]
            else:
                try:
                    e = self._bind_expr(e, scope, views)
                except BindError:
                    # select aliases are visible inside ORDER BY expressions
                    # (q36/q70/q86: `case when lochierarchy = 0 then ...`).
                    # Alias exprs are already bound: shield them behind
                    # placeholders while the rest of the expression binds.
                    placeholders = {}

                    def sub_alias(x):
                        if (
                            isinstance(x, E.Col)
                            and x.table is None
                            and x.name in scope.aliases
                        ):
                            ph = E.Col(self.fresh("_ob"))
                            placeholders[ph.name] = scope.aliases[x.name]
                            return ph
                        return _rewrite_children(x, sub_alias)

                    e = self._bind_expr_partial(
                        sub_alias(e), scope, views, skip=set(placeholders)
                    )
                    for name, repl in placeholders.items():
                        e = _replace_node(e, E.Col(name), repl)
            order_exprs.append((e, it.ascending, it.nulls_first))

        group_exprs = []
        for g in stmt.group_by:
            if isinstance(g, E.Lit) and isinstance(g.value, int):
                group_exprs.append(named_items[g.value - 1][0])
            elif isinstance(g, E.Col) and g.table is None:
                # alias takes precedence only if not a real column
                try:
                    group_exprs.append(self._bind_expr(g, scope, views))
                except BindError:
                    if g.name in scope.aliases:
                        group_exprs.append(scope.aliases[g.name])
                    else:
                        raise
            else:
                group_exprs.append(self._bind_expr(g, scope, views))

        has_agg = (
            bool(group_exprs)
            or any(E.contains_agg(e) for e, _ in named_items)
            or (having is not None and E.contains_agg(having))
            or any(E.contains_agg(e) for e, _, _ in order_exprs)
        )

        if has_agg:
            base, rewrite = self._plan_aggregate(
                base, stmt, group_exprs, named_items, having, order_exprs
            )
            named_items = [(rewrite(e), a) for e, a in named_items]
            having = rewrite(having) if having is not None else None
            order_exprs = [(rewrite(e), asc, nf) for e, asc, nf in order_exprs]

        if having is not None:
            base = P.Filter(having, base)

        # 5. window functions (evaluated over the post-agg relation)
        win_fns = []

        def extract_windows(e):
            if isinstance(e, E.WindowFn):
                for wf, nm in win_fns:
                    if wf == e:
                        return E.Col(nm)
                nm = self.fresh("_w")
                win_fns.append((e, nm))
                return E.Col(nm)
            return _rewrite_children(e, extract_windows)

        named_items = [(extract_windows(e), a) for e, a in named_items]
        order_exprs = [(extract_windows(e), asc, nf) for e, asc, nf in order_exprs]
        if win_fns:
            base = P.Window(win_fns, base)

        # 6. projection (+ hidden sort keys), distinct, sort, limit, prune
        proj_items = []
        out_cols = []
        used = set()
        for e, a in named_items:
            out = a
            while out in used:
                out = self.fresh(a + "_")
            used.add(out)
            proj_items.append((e, out))
            out_cols.append((out, a))
        sort_keys = []
        for e, asc, nf in order_exprs:
            found = None
            for pe, on in proj_items:
                if pe == e:
                    found = on
                    break
            if found is None:
                hn = self.fresh("_s")
                proj_items.append((e, hn))
                found = hn
            sort_keys.append((E.Col(found), asc, nf))

        plan = P.Project(proj_items, base)
        if stmt.distinct:
            plan = P.Distinct(plan)
        if sort_keys and not stmt.set_ops:
            plan = P.Sort(sort_keys, plan)
        if len(proj_items) > len(out_cols):
            plan = P.Project(
                [(E.Col(on), on) for on, _ in out_cols], plan
            )
        if stmt.limit is not None and not stmt.set_ops:
            plan = P.Limit(stmt.limit, plan)
        return plan, out_cols

    # ------------------------------------------------------------------
    def _plan_aggregate(self, base, stmt, group_exprs, named_items, having, order_exprs):
        keys = []
        for g in group_exprs:
            keys.append((g, self.fresh("_g")))
        aggs = []

        def collect(e):
            if isinstance(e, E.Agg):
                for ag, nm in aggs:
                    if ag == e:
                        return
                aggs.append((e, self.fresh("_a")))
                return
            for c in e.children():
                collect(c)

        for e, _ in named_items:
            collect(e)
        if having is not None:
            collect(having)
        for e, _, _ in order_exprs:
            collect(e)
        for e in [e for e, _ in named_items]:
            for w in E.walk(e):
                if isinstance(w, E.WindowFn):
                    for c in w.children():
                        collect(c)

        grouping_sets = None
        if stmt.rollup:
            grouping_sets = [list(range(k)) for k in range(len(keys), -1, -1)]
        elif stmt.grouping_sets is not None:
            # map each raw set member onto the bound group key by structure
            grouping_sets = []
            for s in stmt.grouping_sets:
                idxs = []
                for e in s:
                    for i, g in enumerate(group_exprs):
                        if self._structurally_same(e, g):
                            idxs.append(i)
                            break
                grouping_sets.append(idxs)

        node = P.Aggregate(keys, aggs, base, grouping_sets)

        def rewrite(e):
            if e is None:
                return None
            for g, nm in keys:
                if e == g:
                    return E.Col(nm)
            if isinstance(e, E.Agg):
                for ag, nm in aggs:
                    if ag == e:
                        return E.Col(nm)
                raise BindError(f"unregistered aggregate {e}")
            if isinstance(e, E.WindowFn):
                return dataclasses.replace(
                    e,
                    arg=rewrite(e.arg) if e.arg is not None else None,
                    partition_by=tuple(rewrite(x) for x in e.partition_by),
                    order_by=tuple((rewrite(x), asc) for x, asc in e.order_by),
                )
            if isinstance(e, E.Col):
                raise BindError(
                    f"column {e} is neither grouped nor aggregated"
                )
            return _rewrite_children(e, rewrite)

        return node, rewrite

    def _structurally_same(self, raw, bound):
        # grouping-set member exprs are simple columns in TPC-DS; compare by
        # terminal name
        if isinstance(raw, E.Col) and isinstance(bound, E.Col):
            return bound.name.split(".")[-1] == raw.name or bound.name == raw.name
        return raw == bound

    # ------------------------------------------------------------------
    def _bind_from_item(self, item, outer, views) -> Relation:
        if isinstance(item, A.TableRef):
            name = item.name.lower()
            alias = item.alias or name
            if name in views:
                vplan, vcols = views[name]
                cols = [(qn, a, alias) for qn, a in vcols]
                # re-qualify through a projection so alias.col resolves
                proj = P.Project(
                    [(E.Col(qn), f"{alias}.{a}") for qn, a in vcols], vplan
                )
                return Relation(
                    proj, alias, [(f"{alias}.{a}", a, alias) for _, a in vcols]
                )
            schema = self.catalog.schema(name)
            if schema is None:
                raise BindError(f"unknown table {item.name}")
            cols = [(f"{alias}.{f.name}", f.name, alias) for f in schema]
            return Relation(P.Scan(name, alias), alias, cols)
        if isinstance(item, A.SubqueryRef):
            sub_plan, sub_cols = self.bind_select(item.query, outer, views)
            alias = item.alias
            proj = P.Project(
                [(E.Col(on), f"{alias}.{a}") for on, a in sub_cols], sub_plan
            )
            return Relation(
                proj, alias, [(f"{alias}.{a}", a, alias) for _, a in sub_cols]
            )
        if isinstance(item, A.JoinClause):
            return self._bind_join_clause(item, outer, views)
        raise BindError(f"unsupported FROM item {item}")

    def _bind_join_clause(self, jc: A.JoinClause, outer, views) -> Relation:
        left = self._bind_from_item(jc.left, outer, views)
        right = self._bind_from_item(jc.right, outer, views)
        scope = Scope([left, right], outer)
        lcols = {qn for qn, _, _ in left.columns}
        rcols = {qn for qn, _, _ in right.columns}
        if jc.on is not None:
            cond = self._bind_expr(jc.on, scope, views)
            lkeys, rkeys, residual = _split_equi_conjuncts(
                _conjuncts(cond), lcols, rcols
            )
            res = _conjoin_ast(residual)
        else:
            lkeys, rkeys = [], []
            res = None
        kind = jc.kind
        node = P.Join(kind, left.plan, right.plan, lkeys, rkeys, res)
        cols = list(left.columns) + (
            [] if kind in ("semi", "anti") else list(right.columns)
        )
        return Relation(node, None, cols)

    # ------------------------------------------------------------------
    def _classify_conjunct(
        self, conj, scope, relations, views,
        filters_per_rel, edges, residual, post_join, joinable=None,
    ):
        if joinable is None:
            joinable = set(range(len(relations)))
        # subquery predicates
        subs = [x for x in E.walk(conj) if isinstance(x, E.SubqueryExpr)]
        if subs:
            if len(subs) == 1 and _is_simple_subquery_conjunct(conj, subs[0]):
                post_join.append(
                    self._plan_subquery_conjunct(conj, subs[0], scope, views)
                )
            else:
                # subqueries under OR / multiple per conjunct (TPC-DS q10/q35
                # `exists(...) or exists(...)`): mark joins compute a bool
                # "has match" column per subquery, then the rewritten
                # predicate filters on the marks
                post_join.append(
                    self._plan_marked_conjunct(conj, subs, scope, views)
                )
            return
        bound = self._bind_expr(conj, scope, views)
        refs = _refs(bound)
        rel_sets = [
            {qn for qn, _, _ in r.columns} for r in relations
        ]
        touching = [i for i, s in enumerate(rel_sets) if refs & s]
        if len(touching) <= 1:
            i = touching[0] if touching else 0
            if touching and i not in joinable:
                # references a pending LEFT JOIN's right side: must apply
                # after that join (a WHERE filter on null-extended columns
                # does not commute with the outer join)
                residual.append(bound)
                return
            filters_per_rel[i].append(bound)
            return
        if (
            isinstance(bound, E.BinOp)
            and bound.op == "="
            and len(touching) == 2
            and all(i in joinable for i in touching)
        ):
            i, j = touching
            le, re_ = bound.left, bound.right
            if _refs(le) <= rel_sets[i] and _refs(re_) <= rel_sets[j]:
                edges.append((i, j, le, re_))
                return
            if _refs(le) <= rel_sets[j] and _refs(re_) <= rel_sets[i]:
                edges.append((i, j, re_, le))
                return
        residual.append(bound)

    # ------------------------------------------------------------------
    def _plan_subquery_conjunct(self, conj, sub: E.SubqueryExpr, scope, views):
        """Returns fn(base_plan) -> new_plan implementing the predicate."""
        if sub.kind == "exists":
            inner_plan, joins = self._bind_correlated(sub.query, scope, views)
            resid = self._subquery_residual
            if resid is not None and not joins:
                raise BindError(
                    "correlated non-equi subquery predicate needs at least "
                    "one equi correlation to join on"
                )
            kind = "anti" if _under_not(conj, sub) else "semi"
            lkeys = [o for o, _ in joins]
            rkeys = [i for _, i in joins]
            return lambda base: P.Join(
                kind, base, inner_plan, lkeys, rkeys, resid
            )
        if sub.kind == "in":
            operand = self._bind_expr(sub.operand, scope, views)
            inner_plan, joins = self._bind_correlated(
                sub.query, scope, views
            )
            resid = self._subquery_residual
            sub_cols = self._subquery_out_cols
            negated = sub.negated or _under_not(conj, sub)
            if not negated:
                lkeys = [operand] + [o for o, _ in joins]
                rkeys = [E.Col(sub_cols[0][0])] + [i for _, i in joins]
                return lambda base: P.Join(
                    "semi", base, inner_plan, lkeys, rkeys, resid
                )
            if resid is not None:
                raise BindError(
                    "correlated non-equi predicate under NOT IN is not "
                    "supported"
                )
            mark_specs, pred = self._not_in_lowering(
                operand, inner_plan, joins, sub_cols
            )

            def apply_not_in(base):
                for plan, lk, rk, name in mark_specs:
                    base = P.Join("mark", base, plan, lk, rk, mark_name=name)
                return P.Filter(pred, base)

            return apply_not_in
        if sub.kind == "scalar":
            # conj is CMP(expr, subquery) possibly correlated. Use a unique
            # placeholder for the subquery value so an outer column sharing
            # the subquery's output alias can't collide during binding.
            inner_plan, joins = self._bind_correlated(sub.query, scope, views)
            if self._subquery_residual is not None:
                # the left-join decorrelation below has nowhere to evaluate a
                # non-equi correlated predicate; refuse rather than drop it
                raise BindError(
                    "correlated non-equi predicate in a scalar subquery is "
                    "not supported"
                )
            sub_cols = self._subquery_out_cols
            placeholder = E.Col(self.fresh("_sqv"))
            cmp = _replace_node(conj, sub, placeholder)
            cmp = self._bind_expr_partial(cmp, scope, views, skip={placeholder.name})
            if not joins:
                # uncorrelated: broadcast scalar
                sc = E.ScalarSubquery(plan=inner_plan, out_name=sub_cols[0][0])
                cmp2 = _replace_node(cmp, placeholder, sc)
                return lambda base: P.Filter(cmp2, base)
            cmp = _replace_node(cmp, placeholder, E.Col(sub_cols[0][0]))
            lkeys = [o for o, _ in joins]
            rkeys = [i for _, i in joins]

            def apply(base):
                j = P.Join("left", base, inner_plan, lkeys, rkeys)
                return P.Filter(cmp, j)

            return apply
        raise BindError(f"unsupported subquery kind {sub.kind}")

    def _not_in_lowering(self, operand, inner_plan, joins, sub_cols):
        """3VL-correct NOT IN as mark joins + a boolean predicate.

        `x NOT IN (subquery)` is TRUE iff no inner row (of this row's
        correlation group) equals x, no inner row of the group has a NULL
        value, and either x is non-null or the group is empty. Returns
        (mark_specs, predicate): mark_specs are (plan, lkeys, rkeys, name)
        mark joins to apply to the base, predicate is the replacement expr.
        Group-scoped marks fix the classic global-null-count bug; scalar
        counts are only used when uncorrelated (group == whole subquery)."""
        val = E.Col(sub_cols[0][0])
        lcorr = [o for o, _ in joins]
        rcorr = [i for _, i in joins]
        m_match = self.fresh("_m")
        specs = [(inner_plan, [operand] + lcorr, [val] + rcorr, m_match)]
        null_rows = P.Filter(E.UnaryOp("isnull", val), inner_plan)
        if joins:
            m_null = self.fresh("_m")
            m_any = self.fresh("_m")
            specs.append((null_rows, lcorr, rcorr, m_null))
            specs.append((inner_plan, lcorr, rcorr, m_any))
            has_null = E.Col(m_null)
            has_any = E.Col(m_any)
        else:
            null_cnt = P.Aggregate(
                keys=[], aggs=[(E.Agg("count", None), "_nn")], child=null_rows
            )
            any_cnt = P.Aggregate(
                keys=[], aggs=[(E.Agg("count", None), "_na")], child=inner_plan
            )
            has_null = E.BinOp(
                ">", E.ScalarSubquery(plan=null_cnt, out_name="_nn"), E.Lit(0)
            )
            has_any = E.BinOp(
                ">", E.ScalarSubquery(plan=any_cnt, out_name="_na"), E.Lit(0)
            )
        pred = E.BinOp(
            "and",
            E.BinOp(
                "and",
                E.UnaryOp("not", E.Col(m_match)),
                E.UnaryOp("not", has_null),
            ),
            E.BinOp(
                "or",
                E.UnaryOp("isnotnull", operand),
                E.UnaryOp("not", has_any),
            ),
        )
        return specs, pred

    def _plan_marked_conjunct(self, conj, subs, scope, views):
        """Mark-join lowering for subqueries in arbitrary boolean context."""
        mark_joins = []  # (inner_plan, lkeys, rkeys, mark_name)
        rewritten = conj
        marks = set()
        # local, not instance state: binding an inner subquery below can
        # re-enter this method, which must not drain the outer call's
        # pending placeholder substitutions
        marked_replacements = {}
        for sub in subs:
            if sub.kind == "scalar":
                inner_plan, joins = self._bind_correlated(sub.query, scope, views)
                if joins or self._subquery_residual is not None:
                    raise BindError(
                        "correlated scalar subquery under OR is not supported"
                    )
                # uncorrelated: inline as a broadcast scalar (pre-bound, so
                # protect it behind a placeholder like the NOT IN lowering)
                sc = E.ScalarSubquery(
                    plan=inner_plan, out_name=self._subquery_out_cols[0][0]
                )
                placeholder = E.Col(self.fresh("_sqv"))
                marked_replacements[placeholder.name] = sc
                marks.add(placeholder.name)
                rewritten = _replace_node(rewritten, sub, placeholder)
                continue
            inner_plan, joins = self._bind_correlated(sub.query, scope, views)
            sub_cols = self._subquery_out_cols
            if sub.kind == "in" and sub.negated:
                operand = self._bind_expr(sub.operand, scope, views)
                specs, repl = self._not_in_lowering(
                    operand, inner_plan, joins, sub_cols
                )
                for plan, lk, rk, name in specs:
                    marks.add(name)
                    mark_joins.append((plan, lk, rk, name, None))
                # repl is fully bound already; protect it from re-binding
                placeholder = E.Col(self.fresh("_nip"))
                marked_replacements[placeholder.name] = repl
                marks.add(placeholder.name)
                rewritten = _replace_node(rewritten, sub, placeholder)
                continue
            mark = self.fresh("_m")
            marks.add(mark)
            lkeys = [o for o, _ in joins]
            rkeys = [i for _, i in joins]
            if sub.kind == "in":
                operand = self._bind_expr(sub.operand, scope, views)
                lkeys = [operand] + lkeys
                rkeys = [E.Col(sub_cols[0][0])] + rkeys
            repl = E.Col(mark)
            rewritten = _replace_node(rewritten, sub, repl)
            mark_joins.append(
                (inner_plan, lkeys, rkeys, mark, self._subquery_residual)
            )
        pred = self._bind_expr_partial(rewritten, scope, views, skip=marks)
        for name, repl in marked_replacements.items():
            pred = _replace_node(pred, E.Col(name), repl)

        def apply(base):
            for inner_plan, lkeys, rkeys, mark, resid in mark_joins:
                base = P.Join(
                    "mark", base, inner_plan, lkeys, rkeys, resid,
                    mark_name=mark,
                )
            return P.Filter(pred, base)

        return apply

    def _bind_correlated(self, query: A.SelectStmt, scope, views):
        """Bind a (possibly correlated) subquery.

        Correlated equi-conjuncts referencing the outer scope are stripped
        from the subquery and returned as join pairs (outer_expr, inner_col).
        If the subquery is a scalar aggregate, the correlation columns become
        its GROUP BY keys (classic decorrelation)."""
        corr = []

        sub_binder = _CorrelatedBinder(self, scope, corr, views)
        plan, cols = sub_binder.run(query)
        self._subquery_out_cols = cols
        return plan, corr

    # ------------------------------------------------------------------
    # expression binding
    def _bind_expr(self, e, scope: Scope, views):
        return self._bind_expr_partial(e, scope, views, skip=set())

    def _bind_expr_partial(self, e, scope, views, skip):
        def rec(x):
            if isinstance(x, E.Col):
                if x.name in skip:
                    return x
                qn, _outer = scope.resolve(x.name, x.table)
                return E.Col(qn)
            if isinstance(x, E.SubqueryExpr):
                if x.kind != "scalar":
                    raise BindError(
                        "IN/EXISTS subquery only supported in WHERE conjuncts"
                    )
                inner_plan, joins = self._bind_correlated(x.query, scope, views)
                if joins:
                    raise BindError(
                        "correlated scalar subquery only supported as a "
                        "WHERE comparison"
                    )
                cols = self._subquery_out_cols
                return E.ScalarSubquery(plan=inner_plan, out_name=cols[0][0])
            if isinstance(x, E.ScalarSubquery):
                return x
            return _rewrite_children(x, rec)

        return rec(e)


class _CorrelatedBinder:
    """Binds a subquery, stripping outer-referencing equi-conjuncts into
    correlation join pairs; adds correlation columns to GROUP BY for scalar
    aggregate subqueries."""

    def __init__(self, binder: Binder, outer_scope: Scope, corr_out: list, views=None):
        self.binder = binder
        self.outer = outer_scope
        self.corr = corr_out
        self.views = views or {}

    def run(self, query: A.SelectStmt):
        q = dataclasses.replace(query)
        # Pre-scan WHERE conjuncts for outer references
        inner_probe, _ = _probe_scope(self.binder, q, self.outer, self.views)
        kept = []
        corr_inner_exprs = []
        residual_conjs = []  # correlated NON-equi conjuncts (q16/q94 `<>`)
        if q.where is not None:
            for conj in _conjuncts(q.where):
                pair = self._try_correlated_equi(conj, inner_probe)
                if pair is not None:
                    outer_e, inner_e = pair
                    self.corr.append((outer_e, inner_e))
                    corr_inner_exprs.append(inner_e)
                elif self._refs_outer(conj, inner_probe):
                    residual_conjs.append(conj)
                else:
                    kept.append(conj)
            q.where = _conjoin_ast(kept)
        # binder._subquery_residual is set fresh on every return path below:
        # nested subqueries bound inside bind_select re-enter this method and
        # would otherwise leak their residual onto the enclosing join
        if (self.corr or residual_conjs) and _is_scalar_agg(q):
            if residual_conjs:
                raise BindError(
                    "correlated non-equi predicate in a scalar subquery is "
                    "not supported"
                )
            # group the aggregate by the correlation keys
            q = dataclasses.replace(q, group_by=list(q.group_by))
            plan, cols = self._bind_grouped_scalar(q, corr_inner_exprs)
            self.binder._subquery_residual = None
            return plan, cols
        if self.corr or residual_conjs:
            # expose the inner correlation keys (and any inner columns the
            # non-equi residual needs) through the subquery's own projection
            # (binding them in the subquery scope, where they resolve
            # correctly). The residual itself becomes a join residual on the
            # semi/anti/mark join, evaluated over the pair table where both
            # sides' columns coexist.
            binder = self.binder
            res_inner = []  # raw (name, table) inner Col refs of the residual
            for conj in residual_conjs:
                for x in E.walk(conj):
                    if isinstance(x, E.Col) and self._is_inner(x, inner_probe):
                        key = (x.name, x.table)
                        if key not in [(c.name, c.table) for c in res_inner]:
                            res_inner.append(x)
            extra = list(corr_inner_exprs) + list(res_inner)
            key_aliases = [binder.fresh("_ck") for _ in extra]
            q = dataclasses.replace(
                q,
                select_items=list(q.select_items)
                + [(e, a) for e, a in zip(extra, key_aliases)],
            )
            plan, cols = binder.bind_select(q, self.outer, self.views)
            nk = len(extra)
            val_cols, key_cols = cols[:-nk], cols[-nk:]
            ncorr = len(corr_inner_exprs)
            self.corr[:] = [
                (o, E.Col(kc[0]))
                for (o, _), kc in zip(self.corr, key_cols[:ncorr])
            ]
            bound_residual = None
            if residual_conjs:
                # bind each residual conjunct: inner cols -> their exposed
                # output columns; everything else -> the outer scope
                inner_map = {
                    (c.name, c.table): E.Col(kc[0])
                    for c, kc in zip(res_inner, key_cols[ncorr:])
                }

                def bind_residual(x):
                    if isinstance(x, E.Col):
                        if (x.name, x.table) in inner_map and self._is_inner(
                            x, inner_probe
                        ):
                            return inner_map[(x.name, x.table)]
                        qn, _ = self.outer.resolve(x.name, x.table)
                        return E.Col(qn)
                    return _rewrite_children(x, bind_residual)

                bound_residual = _conjoin(
                    [bind_residual(c) for c in residual_conjs]
                )
            binder._subquery_residual = bound_residual
            binder._subquery_out_cols = val_cols
            return plan, val_cols
        plan, cols = self.binder.bind_select(q, self.outer, self.views)
        self.binder._subquery_residual = None
        return plan, cols

    def _is_inner(self, col: E.Col, inner_probe) -> bool:
        try:
            inner_probe.resolve(col.name, col.table)
            return True
        except BindError:
            return False

    def _refs_outer(self, conj, inner_probe) -> bool:
        """True if the conjunct references at least one outer column."""
        for x in E.walk(conj):
            if isinstance(x, E.Col) and not self._is_inner(x, inner_probe):
                try:
                    self.outer.resolve(x.name, x.table)
                    return True
                except BindError:
                    pass
        return False

    def _bind_grouped_scalar(self, q, corr_inner_exprs):
        binder = self.binder
        # bind the scalar aggregate subquery with corr keys added as group
        # keys and projected out
        plan, cols = binder.bind_select(
            dataclasses.replace(
                q,
                select_items=list(q.select_items)
                + [(e, binder.fresh("_ck")) for e in corr_inner_exprs],
                group_by=list(q.group_by) + list(corr_inner_exprs),
            ),
            None,
            self.views,
        )
        n_keys = len(corr_inner_exprs)
        val_cols = cols[:-n_keys] if n_keys else cols
        key_cols = cols[-n_keys:] if n_keys else []
        self.corr[:] = [
            (o, E.Col(kc[0])) for (o, _), kc in zip(self.corr, key_cols)
        ]
        self.binder._subquery_out_cols = val_cols
        return plan, val_cols

    def _try_correlated_equi(self, conj, inner_probe):
        """If conj is outer_expr = inner_expr, return (bound_outer, raw_inner)."""
        if not (isinstance(conj, E.BinOp) and conj.op == "="):
            return None
        for a, b in ((conj.left, conj.right), (conj.right, conj.left)):
            if not isinstance(a, E.Col):
                continue
            try:
                inner_probe.resolve(a.name, a.table)
                continue  # resolves internally -> not an outer ref
            except BindError:
                pass
            try:
                qn, _ = self.outer.resolve(a.name, a.table)
            except BindError:
                continue
            return (E.Col(qn), b)
        return None


def _probe_scope(binder, q, outer, views=None):
    """Build a name-resolution-only scope for the subquery's FROM items,
    flattening joins and covering base tables, CTE views, and derived
    tables alike (misses here misclassify inner columns as correlations)."""
    views = views or {}
    flat = []
    stack = list(q.from_items)
    while stack:
        it = stack.pop()
        if isinstance(it, A.JoinClause):
            stack += [it.left, it.right]
        else:
            flat.append(it)
    rels = []
    for item in flat:
        if isinstance(item, A.TableRef):
            name = item.name.lower()
            alias = item.alias or name
            if name in views:
                _vplan, vcols = views[name]
                rels.append(
                    Relation(None, alias, [(f"{alias}.{a}", a, alias) for _, a in vcols])
                )
                continue
            schema = binder.catalog.schema(name)
            if schema is None:
                rels.append(Relation(None, alias, []))
            else:
                rels.append(
                    Relation(
                        None,
                        alias,
                        [(f"{alias}.{f.name}", f.name, alias) for f in schema],
                    )
                )
        elif isinstance(item, A.SubqueryRef):
            # approximate: output columns from its select list aliases
            cols = []
            for e, a in item.query.select_items:
                if a:
                    cols.append((f"{item.alias}.{a}", a, item.alias))
                elif isinstance(e, E.Col):
                    cols.append((f"{item.alias}.{e.name}", e.name, item.alias))
            rels.append(Relation(None, item.alias, cols))
    return Scope(rels, None), rels


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def _conjuncts(e):
    if isinstance(e, E.BinOp) and e.op == "and":
        return _conjuncts(e.left) + _conjuncts(e.right)
    return [e]


def _disjuncts(e):
    if isinstance(e, E.BinOp) and e.op == "or":
        return _disjuncts(e.left) + _disjuncts(e.right)
    return [e]


def _factor_or(e):
    """(A and P1) or (A and P2) -> [A, (P1 or P2)]; identity otherwise."""
    if not (isinstance(e, E.BinOp) and e.op == "or"):
        return [e]
    branch_conjs = [_conjuncts(d) for d in _disjuncts(e)]
    common = [
        c
        for c in branch_conjs[0]
        if all(any(c == x for x in s) for s in branch_conjs[1:])
    ]
    if not common:
        return [e]
    remaining = []
    for s in branch_conjs:
        rest = [x for x in s if not any(x == c for c in common)]
        if not rest:
            return list(common)  # one branch is fully covered: OR is vacuous
        remaining.append(_conjoin(rest))
    out = list(common)
    disj = remaining[0]
    for r in remaining[1:]:
        disj = E.BinOp("or", disj, r)
    out.append(disj)
    return out


def _conjoin(preds):
    out = preds[0]
    for p in preds[1:]:
        out = E.BinOp("and", out, p)
    return out


def _conjoin_ast(preds):
    if not preds:
        return None
    return _conjoin(preds)


_refs = E.col_refs


def _null_rejecting_shape(conj):
    """True if the (unbound) conjunct is a comparison that is strict in its
    column references: any NULL operand makes it NULL, i.e. it filters out
    null-extended rows of every relation it touches. Conservative — any
    null-tolerant wrapper (IS NULL, CASE, coalesce) or subquery disqualifies.
    Drives LEFT-JOIN -> INNER promotion in _bind_core."""
    if not (
        isinstance(conj, E.BinOp)
        and conj.op in ("=", "<", ">", "<=", ">=", "<>", "!=")
    ):
        return False
    for x in E.walk(conj):
        if isinstance(x, E.UnaryOp) and x.op in ("isnull", "isnotnull"):
            return False
        if isinstance(x, (E.Case, E.SubqueryExpr, E.ScalarSubquery)):
            return False
        if isinstance(x, E.Func) and x.name.lower() in (
            "coalesce", "ifnull", "nvl",
        ):
            return False
        # null-tolerant boolean connectives nested inside an operand:
        # `a.x = (b.y OR TRUE)` is TRUE even when b.y is NULL, so the
        # comparison is NOT strict in b's columns (three-valued logic lets
        # AND/OR absorb a NULL input)
        if x is not conj and isinstance(x, E.BinOp) and x.op in (
            "and", "or",
        ):
            return False
    return True


def _split_equi_conjuncts(conjuncts, lcols, rcols):
    """Partition bound ON conjuncts into equi-key pairs (left expr over
    lcols, right expr over rcols) and a residual list. Shared by the binary
    join path and the pending-LEFT-JOIN assembly so the two stay in
    lockstep."""
    lkeys, rkeys, residual = [], [], []
    for conj in conjuncts:
        if isinstance(conj, E.BinOp) and conj.op == "=":
            le, re_ = conj.left, conj.right
            refs_l, refs_r = _refs(le), _refs(re_)
            if refs_l and refs_r:
                if refs_l <= lcols and refs_r <= rcols:
                    lkeys.append(le)
                    rkeys.append(re_)
                    continue
                if refs_l <= rcols and refs_r <= lcols:
                    lkeys.append(re_)
                    rkeys.append(le)
                    continue
        residual.append(conj)
    return lkeys, rkeys, residual


def _rewrite_children(e, fn):
    if isinstance(e, E.BinOp):
        return E.BinOp(e.op, fn(e.left), fn(e.right))
    if isinstance(e, E.UnaryOp):
        return E.UnaryOp(e.op, fn(e.operand))
    if isinstance(e, E.Between):
        return E.Between(fn(e.operand), fn(e.low), fn(e.high), e.negated)
    if isinstance(e, E.InList):
        return E.InList(fn(e.operand), e.values, e.negated)
    if isinstance(e, E.Like):
        return E.Like(fn(e.operand), e.pattern, e.negated)
    if isinstance(e, E.Case):
        return E.Case(
            tuple((fn(c), fn(v)) for c, v in e.branches),
            fn(e.default) if e.default is not None else None,
        )
    if isinstance(e, E.Cast):
        return E.Cast(fn(e.operand), e.target)
    if isinstance(e, E.Func):
        return E.Func(e.name, tuple(fn(a) for a in e.args))
    if isinstance(e, E.Agg):
        return E.Agg(e.fn, fn(e.arg) if e.arg is not None else None, e.distinct)
    if isinstance(e, E.WindowFn):
        return E.WindowFn(
            e.fn,
            fn(e.arg) if e.arg is not None else None,
            tuple(fn(x) for x in e.partition_by),
            tuple((fn(x), asc) for x, asc in e.order_by),
            e.frame,
        )
    return e


def _is_simple_subquery_conjunct(conj, sub):
    """True when replacing the whole conjunct by a join is semantics-preserving:
    the subquery is the entire conjunct (under optional NOT) for EXISTS/IN,
    or any shape for scalar (the scalar path filters the full rewritten
    predicate, so OR contexts stay correct)."""
    if sub.kind == "scalar":
        return True
    e = conj
    while isinstance(e, E.UnaryOp) and e.op == "not":
        e = e.operand
    return e is sub


def _find_subquery(e):
    for x in E.walk(e):
        if isinstance(x, E.SubqueryExpr):
            return x
    return None


def _under_not(conj, sub):
    """True if the subquery appears under a NOT (NOT EXISTS ...)."""
    def rec(e, neg):
        if e is sub:
            return neg
        if isinstance(e, E.UnaryOp) and e.op == "not":
            return rec(e.operand, not neg)
        for c in e.children():
            r = rec(c, neg)
            if r is not None:
                return r
        return None

    r = rec(conj, False)
    return bool(r)


def _replace_node(e, target, replacement):
    if e is target or e == target:
        return replacement

    def fn(x):
        return _replace_node(x, target, replacement)

    return _rewrite_children(e, fn)


def _is_scalar_agg(q: A.SelectStmt) -> bool:
    return (
        len(q.select_items) == 1
        and q.select_items[0][0] != "*"
        and E.contains_agg(q.select_items[0][0])
        and not q.group_by
    )
