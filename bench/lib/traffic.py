"""The one traffic generator: it reads a mix's parameters from
``bench/traffic/<name>.json`` and makes, from the seed, the warm-up requests
and the window's schedule.

Three kinds of mix, each set out by its file alone:

* ``sessions`` — open loop.  Analyst drill sessions: each session fixes a
  filter (a year and a region) and walks a hierarchy through ``steps``
  (fine query, roll-ups, repeats, a drill-down).  Session specs are ranked by
  a Zipf law over (hierarchy x year x region); popular specs recur.  The
  arrivals are shared round-robin by ``sessions_open`` concurrent sessions.
* ``shapes`` — open loop.  Each request is one of the ``shapes`` (a template
  of levels, measures and filters with ``$params``), with fresh literals.
* ``dashboard`` — closed loop.  ``clients`` each submit a dashboard of
  ``tiles`` requests over one (levels, measures) pair and wait for it; the
  tiles' literals are drawn from the product space of ``space`` without
  replacement, for a pool of at most ``pool_dashboards`` dashboards that
  no window may use up.

Any mix may set ``nl_share`` in [0, 1]: that share of its requests, warm-up
included, is sent as a plain-English question (``bench/lib/nl.py``) of the
same intent in place of its SQL.  Which requests are questions comes from a
stream of its own (``NL``), the same for every seed; a mix without
``nl_share`` makes exactly the schedule it made before questions existed.

Every seed gets the same work in the same order: the arrival times, the
order of session ranks and of shapes come from one fixed stream (``SHAPE``),
and the seed draws only the literals (which year and region each session
rank filters on, each shape's parameters, the dashboards' filters) and the
data.  So two seeds differ in what is asked, not in how much or when.
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
from typing import Optional

import numpy as np

from . import nl
from .data import BENCH, Data, rng_for
from .reference import render_expr

SHAPE = 0  # the seed of the stream that fixes arrivals and orders
NL = 106  # the stream of SHAPE that picks the requests sent as questions


@dataclasses.dataclass
class Request:
    rid: int
    intent: dict
    sql: str
    kind: str  # a name for the request's role: session step or shape
    due: float = 0.0  # seconds after the window opens (open loop)
    group: int = 0  # dashboard number (closed loop) or session number
    nl: Optional[str] = None  # the question sent in place of ``sql``, if any


@dataclasses.dataclass
class Schedule:
    loop: str  # 'open' | 'closed'
    warmup: list[list[Request]]  # submits made during set-up
    requests: list[Request]  # open loop: in due order
    dashboards: list[list[Request]]  # closed loop: in the order clients take them
    workers: int = 1
    clients: int = 1


def load(name: str) -> dict:
    with open(os.path.join(BENCH, "traffic", f"{name}.json")) as f:
        return json.load(f)


# ---------------------------------------------------------------- SQL text
def render_sql(intent: dict, data: Data) -> str:
    """SQL for an intent over the benchmark's star schema."""
    dims: list[str] = []
    cols = list(intent["levels"]) + [f[0] for f in intent["filters"]]
    for _, e in intent["measures"]:
        cols += _expr_cols(e)
    for c in cols:
        t = c.split(".", 1)[0]
        if t != data.fact and t not in dims:
            dims.append(t)
    sel = [lv.split(".", 1)[1] for lv in intent["levels"]]
    sel += [f"{agg}({render_expr(e)}) AS m{i}"
            for i, (agg, e) in enumerate(intent["measures"])]
    sql = f"SELECT {', '.join(sel)} FROM {data.fact}"
    for t in dims:
        pk = next(iter(data.tables[t]))
        sql += f" JOIN {t} ON {data.fact}.{data.fks[t]} = {t}.{pk}"
    if intent["filters"]:
        sql += " WHERE " + " AND ".join(_pred(f) for f in intent["filters"])
    if intent["levels"]:
        sql += " GROUP BY " + ", ".join(lv.split(".", 1)[1] for lv in intent["levels"])
    return sql


def _expr_cols(e) -> list[str]:
    if e == "*":
        return []
    if isinstance(e, str):
        return [e]
    return _expr_cols(e[1]) + _expr_cols(e[2])


def _lit(v) -> str:
    return f"'{v}'" if isinstance(v, str) else str(v)


def _pred(f) -> str:
    col, op, val = f[0].split(".", 1)[1], f[1], f[2]
    if op == "between":
        return f"{col} BETWEEN {_lit(val[0])} AND {_lit(val[1])}"
    if op == "in":
        return f"{col} IN ({', '.join(_lit(v) for v in val)})"
    return f"{col} {op} {_lit(val)}"


# ------------------------------------------------------------- parameters
def draw_params(spec: dict, rng: np.random.Generator) -> dict:
    """Draw ``{name: value}`` in file order: ``{"int": [lo, hi]}``
    (inclusive), ``{"choice": [...]}``, ``{"add": [name, k]}``,
    ``{"fmt": "text with {name}"}`` (``"as": "int"`` makes it a number)."""
    out: dict = {}
    for name, d in spec.items():
        if "int" in d:
            out[name] = int(rng.integers(d["int"][0], d["int"][1] + 1))
        elif "choice" in d:
            out[name] = d["choice"][int(rng.integers(len(d["choice"])))]
        elif "add" in d:
            out[name] = out[d["add"][0]] + d["add"][1]
        elif "fmt" in d:
            out[name] = d["fmt"].format(**out)
            if d.get("as") == "int":
                out[name] = int(out[name])
        else:
            raise ValueError(f"unknown parameter kind in {d}")
    return out


def bind(template: dict, params: dict) -> dict:
    """An intent from a template whose filter values may be ``$name``."""

    def sub(v):
        if isinstance(v, str) and v.startswith("$"):
            return params[v[1:]]
        if isinstance(v, list):
            return [sub(x) for x in v]
        return v

    return {"levels": list(template.get("levels", [])),
            "measures": [list(m) for m in template["measures"]],
            "filters": [[f[0], f[1], sub(f[2])] for f in template.get("filters", [])]}


# ----------------------------------------------------------------- arrivals
def arrivals(rate: float, seconds: float, rng: np.random.Generator) -> np.ndarray:
    """Due times in [0, seconds): ``round(rate * seconds)`` requests whose
    gaps are the midpoint quantiles of an exponential law (Poisson arrivals)
    in an order drawn from ``rng``, scaled to end inside the window."""
    n = max(1, int(round(rate * seconds)))
    q = (np.arange(n) + 0.5) / n
    gaps = (-np.log1p(-q))[rng.permutation(n)]
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]]) * (seconds / gaps.sum())


def zipf_counts(n_ranks: int, total: int, s: float) -> np.ndarray:
    """Sessions per rank: ``total`` shared by a Zipf law, largest remainder."""
    p = 1.0 / np.arange(1, n_ranks + 1) ** s
    p /= p.sum()
    raw = p * total
    counts = np.floor(raw).astype(int)
    short = total - counts.sum()
    counts[np.argsort(-(raw - counts), kind="stable")[:short]] += 1
    return counts


# ------------------------------------------------------------------ kinds
def _sessions(mix, data, seed, seconds):
    hier = mix["hierarchies"]
    years, regions = mix["years"], mix["regions"]
    combos = [(y, r) for y in years for r in regions]
    rng = rng_for(seed, 101)
    # rank -> (hierarchy, year, region): hierarchies take turns along the
    # ranks, the seed decides which filter each rank gets
    perms = [rng.permutation(len(combos)) for _ in hier]
    n_ranks = len(hier) * len(combos)
    specs = [(k % len(hier), combos[perms[k % len(hier)][k // len(hier)]])
             for k in range(n_ranks)]

    def session(spec, sid):
        h = hier[spec[0]]
        year, region = spec[1]
        filters = [[mix["year_column"], "=", year], [h["region_column"], "=", region]]
        names = {"fine": h["levels"][0], "mid": h["levels"][1], "coarse": h["levels"][2]}
        out = []
        for step in mix["steps"]:
            levels = h["drill"] if step == ["drill"] else [names[s] for s in step]
            intent = {"levels": list(levels), "measures": mix["measures"], "filters": filters}
            out.append((intent, f"{h['name']}:{'+'.join(step)}", sid))
        return out

    warm = []
    for i, h in enumerate(hier):
        spec = (i, combos[(len(combos) - 1 - i) % len(combos)])
        warm.append([_req(intent, data, kind) for intent, kind, _ in session(spec, -1)])
    fixed = rng_for(SHAPE, 111)
    due = arrivals(mix["rate_per_s"], seconds, fixed)
    n_slots = mix["sessions_open"]
    per_slot = -(-len(due) // n_slots)
    n_sessions = n_slots * -(-per_slot // len(mix["steps"]))
    counts = zipf_counts(n_ranks, n_sessions, mix["zipf_s"])
    order = np.repeat(np.arange(n_ranks), counts)[fixed.permutation(n_sessions)]
    slots: list[list] = [[] for _ in range(n_slots)]
    nxt = 0
    reqs = []
    for i, t in enumerate(due):
        slot = slots[i % n_slots]
        if not slot:
            slot.extend(session(specs[order[nxt]], nxt))
            nxt += 1
        intent, kind, sid = slot.pop(0)
        reqs.append(_req(intent, data, kind, due=float(t), group=sid, rid=i))
    return warm, reqs


def _shapes(mix, data, seed, seconds):
    shapes = mix["shapes"]
    names = list(shapes)
    rng = rng_for(seed, 102)
    wrng = rng_for(SHAPE, 103)
    warm = [[_req(bind(shapes[n], draw_params(shapes[n].get("params", {}), wrng)),
                  data, n)] for n in names]
    fixed = rng_for(SHAPE, 112)
    due = arrivals(mix["rate_per_s"], seconds, fixed)
    which = np.resize(np.arange(len(names)), len(due))[fixed.permutation(len(due))]
    reqs = []
    for i, (t, k) in enumerate(zip(due, which)):
        s = shapes[names[k]]
        reqs.append(_req(bind(s, draw_params(s.get("params", {}), rng)), data,
                         names[k], due=float(t), rid=i))
    return warm, reqs


def _dashboard(mix, data, seed):
    space = mix["space"]
    axes = []
    for name, d in space.items():
        if "choice" in d:
            axes.append([{name: v} for v in d["choice"]])
        elif "ranges" in d:
            lo, hi = d["ranges"]
            axes.append([{f"{name}_lo": a, f"{name}_hi": b}
                         for a in range(lo, hi + 1) for b in range(a, hi + 1)])
        else:
            raise ValueError(f"unknown space axis {d}")
    total = math.prod(len(a) for a in axes)
    rng = rng_for(seed, 104)
    perm = rng.permutation(total)
    tile = {"levels": mix["levels"], "measures": mix["measures"], "filters": mix["filters"]}

    def point(i):
        params = {}
        for a in reversed(axes):
            params.update(a[i % len(a)])
            i //= len(a)
        return params

    k = mix["tiles"]
    n_dash = min(total // k, mix["pool_dashboards"])
    dashboards = []
    rid = 0
    for d in range(n_dash):
        tiles = []
        for j in range(k):
            tiles.append(_req(bind(tile, point(int(perm[d * k + j]))), data, "tile",
                              group=d, rid=rid))
            rid += 1
        dashboards.append(tiles)
    wperm = rng_for(SHAPE, 105).permutation(total)
    warm = [[_req(bind(tile, point(int(wperm[j]))), data, "tile") for j in range(k)]]
    return warm, dashboards


def _req(intent, data, kind, due=0.0, group=0, rid=-1) -> Request:
    return Request(rid, intent, render_sql(intent, data), kind, due, group)


def _questions(mix: dict, submits: list[list[Request]]) -> None:
    """Send ``nl_share`` of the requests, in submit order, as questions."""
    share = float(mix.get("nl_share", 0.0))
    if not 0.0 <= share <= 1.0:
        raise ValueError(f"nl_share {share} is not in [0, 1]")
    if share == 0.0:
        return
    pick = rng_for(SHAPE, NL)
    for reqs in submits:
        for r in reqs:
            if pick.random() < share:
                r.nl = nl.question(r.intent)


def schedule(name: str, data: Data, seed: int, seconds: float,
             mix: Optional[dict] = None) -> Schedule:
    mix = load(name) if mix is None else mix
    if mix["kind"] == "sessions":
        warm, reqs = _sessions(mix, data, seed, seconds)
        out = Schedule("open", warm, reqs, [], workers=mix.get("workers", 1))
    elif mix["kind"] == "shapes":
        warm, reqs = _shapes(mix, data, seed, seconds)
        out = Schedule("open", warm, reqs, [], workers=mix.get("workers", 1))
    elif mix["kind"] == "dashboard":
        warm, dash = _dashboard(mix, data, seed)
        out = Schedule("closed", warm, [], dash, clients=mix["clients"])
    else:
        raise ValueError(f"unknown traffic kind {mix['kind']!r}")
    _questions(mix, out.warmup + [[r] for r in out.requests] + out.dashboards)
    return out


def intent_key(intent: dict) -> str:
    return json.dumps(intent, sort_keys=True)

