"""Plain reference of SLOTH's pod verdict, in numpy and float64.

It imports nothing of the program.  It is a copy of the arithmetic of the
numpy oracle and the host detection path of the program as the benchmark
was written (``core/sketch.py``, ``core/probes.py``, ``core/recorder.py``,
``core/routing.py`` ``Mesh2D``, ``core/detection.py``, ``core/mcg.py``,
``core/failrank.py`` and ``PodDetector._verdict_from``), so that what the
timed path produces can be compared with it:

* ``Stream`` is the streaming Fail-Slow Sketch (Algorithm 1 with the
  exact run insertion) for both sides of a pod telemetry window;
* ``verdict`` runs core and link detection, the MCG and FailRank over the
  stream's patterns and folds them into one verdict.

Two departures, both exact rewrites: the EM over the path system works
on the path lists instead of a dense ``[events x links]`` matrix, and
FailRank iterates in numpy.  ``lowp=True`` rounds every statistic the
sketch accumulates, and FailRank's state, to bfloat16: the control that
a comparison must fail.
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np

_MASK32 = 0xFFFFFFFF
HASH_A1 = (0x9E3779B1, 0x85EBCA77, 0xC2B2AE3D, 0x27D4EB2F,
           0x165667B1, 0xD3A2646D, 0xFD7046C5, 0xB55A4F09)
HASH_A2 = (0x632BE59B, 0x9E3779B9, 0x7F4A7C15, 0xF39CC060,
           0x1F83D9AB, 0x5BE0CD19, 0xCA62C1D6, 0x8F1BBCDC)
HASH_B = (0x7ED55D16, 0xC761C23C, 0x165667B1, 0xD3A2646C,
          0xFD7046C5, 0xB55A4F09, 0x2DEB33A5, 0x14292967)
SLOT_BYTES = 4 + 4 + 4 + 4 * 4 + 8 + 4
COMP_TAG = 1 << 62
COMM_TAG = 1 << 61
COMP_RECORD_BYTES = 48
COMM_RECORD_BYTES = 56
PACKET_BYTES = 1024
MAX_PACKETS = 64


def _bf16(x):
    import ml_dtypes
    return float(np.asarray(x, dtype=ml_dtypes.bfloat16))


def _bf16_array(x):
    import ml_dtypes
    return np.asarray(x, dtype=ml_dtypes.bfloat16).astype(np.float64)


# ---------------------------------------------------------------------------
# the sketch
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Pattern:
    key: int
    count: int
    sum_dur: float
    sum_sq_dur: float
    sum_val: float
    t_first: float
    t_last: float
    arrival: int
    min_dur: float


def _bucket(lo: int, hi: int, table: int, m: int) -> int:
    x = (HASH_A1[table] * lo + HASH_A2[table] * hi + HASH_B[table]) & _MASK32
    x ^= x >> 16
    x = (x * 0x45D9F3B) & _MASK32
    x ^= x >> 13
    return (x & 0x7FFFFFFF) % m


def _merge(into: dict[int, Pattern], p: Pattern, rnd) -> None:
    q = into.get(p.key)
    if q is None:
        into[p.key] = dataclasses.replace(p)
        return
    q.count += p.count
    q.sum_dur = rnd(q.sum_dur + p.sum_dur)
    q.sum_sq_dur = rnd(q.sum_sq_dur + p.sum_sq_dur)
    q.sum_val = rnd(q.sum_val + p.sum_val)
    q.t_first = min(q.t_first, p.t_first)
    q.t_last = max(q.t_last, p.t_last)
    q.min_dur = min(q.min_dur, p.min_dur)
    q.arrival = min(q.arrival, p.arrival)


class Sketch:
    """Stage-1 majority counters and the FIFO Stage-2 list, with the
    drained (evicted) patterns kept for analysis."""

    def __init__(self, d: int, m: int, H: int, L: int, lowp: bool = False):
        self.d, self.m, self.H, self.L = d, m, H, L
        self.rnd = _bf16 if lowp else float
        self.tab: list[dict[int, list]] = [dict() for _ in range(d)]
        self.stage2: dict[int, Pattern] = {}
        self.drained: list[Pattern] = []
        self.arrival = 0

    def insert_run(self, key: int, r: int, dur: float, val: float,
                   t0: float, dt: float) -> None:
        lo, hi = key & 0x7FFFFFFF, (key >> 31) & 0x7FFFFFFF
        first = r
        for i in range(self.d):
            j = _bucket(lo, hi, i, self.m)
            cell = self.tab[i].get(j)       # [lo, hi, freq] or None
            if cell is not None and cell[0] == lo and cell[1] == hi:
                f0 = cell[2]
                cell[2] = f0 + r
                k = self.H - f0 - 1
            elif cell is None:
                self.tab[i][j] = [lo, hi, r]
                k = self.H - 1
            else:
                f0 = cell[2]
                if r <= f0:
                    cell[2] = f0 - r
                    if cell[2] == 0:
                        del self.tab[i][j]
                    k = r
                else:
                    self.tab[i][j] = [lo, hi, r - f0]
                    k = f0 + self.H - 1
            first = min(first, max(k, 0))
        if first < r:
            n = r - first
            rnd = self.rnd
            d_, v_ = rnd(dur), rnd(val)
            t_first = t0 + dt * first
            t_last = t0 + dt * (r - 1) + dur
            self._touch(key, n, d_, t_first, t_last, rnd(n * d_),
                        rnd(n * d_ * d_), rnd(n * v_))

    def _touch(self, key, n, dur, t_first, t_last, s, sq, v) -> None:
        p = self.stage2.get(key)
        if p is not None:
            rnd = self.rnd
            p.count += n
            p.sum_dur = rnd(p.sum_dur + s)
            p.sum_sq_dur = rnd(p.sum_sq_dur + sq)
            p.sum_val = rnd(p.sum_val + v)
            p.t_first = min(p.t_first, t_first)
            p.t_last = max(p.t_last, t_last)
            p.min_dur = min(p.min_dur, dur)
            return
        if len(self.stage2) >= self.L:
            # arrivals rise in insertion order, so the oldest is first
            victim = self.stage2.pop(next(iter(self.stage2)))
            self.drained.append(victim)
        self.stage2[key] = Pattern(key, n, s, sq, v, t_first, t_last,
                                   self.arrival, dur)
        self.arrival += 1

    def patterns(self) -> list[Pattern]:
        merged: dict[int, Pattern] = {}
        for p in self.drained + list(self.stage2.values()):
            _merge(merged, p, self.rnd)
        return sorted(merged.values(), key=lambda p: p.arrival)

    def compressed_bytes(self) -> int:
        return (self.d * self.m * 12 + self.L * SLOT_BYTES
                + len(self.drained) * SLOT_BYTES)


def comp_keys(comp) -> np.ndarray:
    fb = np.clip(np.log2(np.maximum(comp["flops"], 1.0)).astype(np.int64),
                 0, 63)
    return (comp["core"].astype(np.int64)
            + (comp["stage"].astype(np.int64) << 12)
            + (comp["op"].astype(np.int64) << 28) + (fb << 34) + COMP_TAG)


def comm_keys(comm) -> np.ndarray:
    vb = np.clip(np.log2(np.maximum(comm["bytes"], 1.0)).astype(np.int64),
                 0, 63)
    return (comm["src"].astype(np.int64)
            + (comm["dst"].astype(np.int64) << 12)
            + (comm["stage"].astype(np.int64) << 24) + (vb << 40) + COMM_TAG)


class Stream:
    """Both sides of the streaming recorder at one instruction per task
    and no per-hop latency, as the pod detector runs it."""

    def __init__(self, sketch: dict, lowp: bool = False):
        self.comp = Sketch(**sketch, lowp=lowp)
        self.comm = Sketch(**sketch, lowp=lowp)
        self.n_comp = 0
        self.n_comm = 0
        self.elapsed = 0.0

    def observe(self, comp: dict, comm: dict) -> None:
        if len(comp["core"]):
            keys = comp_keys(comp)
            durs = comp["t_end"] - comp["t_start"]
            for k, d_, v, t0 in zip(keys.tolist(), durs.tolist(),
                                    comp["flops"].tolist(),
                                    comp["t_start"].tolist()):
                self.comp.insert_run(k | COMP_TAG, 1, d_, v, t0, d_)
            self.n_comp += len(keys)
            self.elapsed = max(self.elapsed, float(np.max(comp["t_end"])))
        if len(comm["src"]):
            keys = comm_keys(comm)
            pk = np.clip(np.ceil(comm["bytes"] / PACKET_BYTES)
                         .astype(np.int64), 1, MAX_PACKETS)
            per = np.maximum(comm["service"], 0.0) / pk
            wall = (comm["t_arrive"] - comm["t_depart"]) / pk
            vals = comm["bytes"] / pk
            for k, r, d_, v, t0, dt in zip(
                    keys.tolist(), pk.tolist(), per.tolist(), vals.tolist(),
                    comm["t_depart"].tolist(), wall.tolist()):
                self.comm.insert_run(k, r, d_, v, t0, dt)
            self.n_comm += int(pk.sum())
            self.elapsed = max(self.elapsed,
                               float(np.max(comm["t_arrive"])))

    def sketch_bytes(self) -> int:
        return self.comp.compressed_bytes() + self.comm.compressed_bytes()


# ---------------------------------------------------------------------------
# the mesh
# ---------------------------------------------------------------------------

class Mesh:
    """W x H mesh, directed links to the 4-neighbours, XY routing."""

    def __init__(self, w: int, h: int):
        self.w, self.h = w, h
        self.n_cores = w * h
        self.ids: dict[tuple[int, int], int] = {}
        for y in range(h):
            for x in range(w):
                u = y * w + x
                for dx, dy in ((1, 0), (-1, 0), (0, 1), (0, -1)):
                    if 0 <= x + dx < w and 0 <= y + dy < h:
                        self.ids[(u, (y + dy) * w + x + dx)] = len(self.ids)
        self.n_links = len(self.ids)

    def route(self, s: int, d: int) -> list[int]:
        x, y = s % self.w, s // self.w
        x1, y1 = d % self.w, d // self.w
        path = []
        while x != x1:
            nx = x + (1 if x1 > x else -1)
            path.append(self.ids[(y * self.w + x, y * self.w + nx)])
            x = nx
        while y != y1:
            ny = y + (1 if y1 > y else -1)
            path.append(self.ids[(y * self.w + x, ny * self.w + x)])
            y = ny
        return path


# ---------------------------------------------------------------------------
# detection
# ---------------------------------------------------------------------------

def _gammp(a: float, x: float) -> float:
    if x <= 0.0:
        return 0.0
    if x < a + 1.0:
        ap, s, d = a, 1.0 / a, 1.0 / a
        for _ in range(200):
            ap += 1.0
            d *= x / ap
            s += d
            if abs(d) < abs(s) * 1e-12:
                break
        return s * math.exp(-x + a * math.log(x) - math.lgamma(a))
    b, c, dd, h = x + 1.0 - a, 1e308, 1.0 / (x + 1.0 - a), 1.0 / (x + 1.0 - a)
    for i in range(1, 200):
        an = -i * (i - a)
        b += 2.0
        dd = an * dd + b
        dd = b + an / c if abs(dd) < 1e-300 else dd
        c = b + an / c
        c = 1e-300 if abs(c) < 1e-300 else c
        dd = 1.0 / dd
        delta = dd * c
        h *= delta
        if abs(delta - 1.0) < 1e-12:
            break
    return 1.0 - math.exp(-x + a * math.log(x) - math.lgamma(a)) * h


def _windows(t_mid, total, n):
    w = np.floor(t_mid / max(total, 1e-12) * n).astype(np.int64)
    return np.clip(w, 0, n - 1)


def detect_cores(pats, total, n_windows=4, z_flag=6.0, min_group=3):
    """``{(core, window): (prob, z)}`` of cores slower than their
    volume-equivalent peers (median/MAD outliers per group)."""
    out: dict[tuple[int, int], tuple[float, float]] = {}
    if not pats:
        return out
    keys = np.array([p.key for p in pats], dtype=np.int64)
    cores = keys & 0xFFF
    sig = keys >> 12
    rate = np.array([p.sum_val / max(p.sum_dur, 1e-12) for p in pats])
    win = _windows(np.array([(p.t_first + p.t_last) / 2 for p in pats]),
                   total, n_windows)
    order = np.argsort(sig, kind="stable")
    bounds = np.nonzero(np.diff(sig[order]) != 0)[0] + 1
    for grp in np.split(order, bounds):
        if len(grp) < min_group:
            continue
        r = rate[grp]
        med = np.median(r)
        sigma = max(np.median(np.abs(r - med)) * 1.4826, 0.02 * med, 1e-12)
        for gi, zi in zip(grp, (med - r) / sigma):
            if zi <= 0:
                continue
            prob = 1.0 / (1.0 + math.exp(-(zi - z_flag)))
            k = (int(cores[gi]), int(win[gi]))
            if k not in out or prob > out[k][0]:
                out[k] = (float(prob), float(zi))
    return out


def detect_links(pats, mesh: Mesh, total, n_windows=4, ratio_flag=3.0,
                 em_iters=60):
    """``(theta [windows, links], observed, {(link, window): (prob, r)})``:
    a global EM for each link's inverse bandwidth, then per-window
    slowdown estimates judged against a Gamma model of the healthy ones."""
    n_l = mesh.n_links
    theta = np.zeros((n_windows, n_l))
    observed = np.zeros((n_windows, n_l), dtype=bool)
    cands: dict[tuple[int, int], tuple[float, float]] = {}
    if not pats:
        return theta, observed, cands
    keys = np.array([p.key for p in pats], dtype=np.int64)
    src, dst = keys & 0xFFF, (keys >> 12) & 0xFFF
    inter = np.nonzero(src != dst)[0]
    if not len(inter):
        return theta, observed, cands
    T = np.array([pats[i].min_dur for i in inter])
    V = np.array([pats[i].sum_val / max(pats[i].count, 1) for i in inter])
    W = np.array([float(pats[i].count) for i in inter])
    win = _windows(np.array([(pats[i].t_first + pats[i].t_last) / 2
                             for i in inter]), total, n_windows)
    rows, cols = [], []
    for e, i in enumerate(inter):
        for lid in sorted(set(mesh.route(int(src[i]), int(dst[i])))):
            rows.append(e)
            cols.append(lid)
    rows, cols = np.asarray(rows), np.asarray(cols)
    n_e = len(inter)
    T = np.maximum(T, 1e-12)

    def per_event(x_links):            # sum over each event's links
        return np.bincount(rows, weights=x_links[cols], minlength=n_e)

    def per_link(x_events):            # sum over each link's events
        return np.bincount(cols, weights=x_events[rows], minlength=n_l)

    hops = np.bincount(rows, minlength=n_e).astype(np.float64)
    traffic = per_link(W * V)
    seen = traffic > 0
    th = np.full(n_l, float((T / np.maximum(hops * V, 1e-12)).mean()))
    for _ in range(em_iters):
        pred = V * per_event(th)
        ratio = T / np.maximum(pred, 1e-300)
        th = np.where(seen, th * per_link(W * V * ratio)
                      / np.maximum(traffic, 1e-300), th)
    n_ev = np.bincount(cols, minlength=n_l)
    if seen.any():
        lam = n_ev / (n_ev + 3.0)
        th = np.where(seen, lam * th + (1 - lam) * np.median(th[seen]), th)
    seen_any = n_ev > 0
    th = np.maximum(th, 0.05 * np.median(th[seen_any]))
    pred = V * per_event(th)
    ratio_e = np.clip(T / np.maximum(pred, 1e-300), 0.0, 50.0)
    share = V[rows] * th[cols] / np.maximum(pred, 1e-300)[rows]
    ratios = np.ones((n_windows, n_l))
    keep = share >= 0.15
    for w in range(n_windows):
        sel = keep & (win[rows] == w)
        ev_rows, ev_cols, ev_share = rows[sel], cols[sel], share[sel]
        for li in np.unique(ev_cols):
            m = ev_cols == li
            if m.sum() < 3:
                continue
            est = np.maximum((ratio_e[ev_rows[m]] - 1.0) / ev_share[m] + 1.0,
                             0.1)
            ratios[w, li] = max(float(np.median(est)), 0.25)
            observed[w, li] = True
        theta[w] = np.where(observed[w], th * ratios[w], 0.0)
    pop = float(np.median(th[seen_any]))
    norm = np.where(observed, theta / max(pop, 1e-300), 1.0)
    pool = norm[observed]
    shape = scale = None
    if len(pool) >= 8:
        lo = pool[pool <= np.quantile(pool, 0.9)]
        mu, var = float(lo.mean()), float(max(lo.var(), 1e-6))
        if mu > 0:
            shape, scale = mu * mu / var, var / mu
    for w in range(n_windows):
        for li in np.nonzero(observed[w])[0]:
            r = float(norm[w, li])
            if r <= ratio_flag * 0.6:
                continue
            prob = 1.0 / (1.0 + math.exp(-1.5 * (r - ratio_flag)))
            if shape is not None:
                prob *= 1.0 - (1.0 - _gammp(shape, max(r, 0.0) / scale))
            cands[(int(li), w)] = (float(prob), r)
    return theta, observed, cands


# ---------------------------------------------------------------------------
# MCG and FailRank
# ---------------------------------------------------------------------------

def failrank_scores(comm_pats, mesh: Mesh, total, core_c, link_c, theta,
                    n_windows=4, lowp=False, lam=0.55, alpha=0.1, beta=0.3,
                    gamma=0.6, eps=1e-4, max_iters=100):
    """Raw FailRank node scores and the link scores folded back onto the
    links, on the multi-level communication graph of the comm patterns."""
    nc = mesh.n_cores
    n_nodes = n_windows * nc + n_windows
    traffic: dict[tuple[int, int, int], float] = {}
    if comm_pats:
        keys = np.array([p.key for p in comm_pats], dtype=np.int64)
        src, dst = keys & 0xFFF, (keys >> 12) & 0xFFF
        win = _windows(np.array([(p.t_first + p.t_last) / 2
                                 for p in comm_pats]), total, n_windows)
        for s, d, p, w in zip(src.tolist(), dst.tolist(), comm_pats,
                              win.tolist()):
            if s != d:
                k = (w, s, d)
                traffic[k] = traffic.get(k, 0.0) + float(p.sum_val)
    e_src, e_dst, e_vol, paths = [], [], [], []
    for (w, s, d), v in sorted(traffic.items()):
        e_src.append(w * nc + s)
        e_dst.append(w * nc + d)
        e_vol.append(v)
        paths.append(mesh.route(s, d))
    mean_vol = float(np.mean(e_vol)) if e_vol else 1.0
    active: dict[int, set[int]] = {w: set() for w in range(n_windows)}
    for (w, s, d) in traffic:
        active[w].update((s, d))
    for w in range(n_windows - 1):
        dram = n_windows * nc + w
        for c in sorted(active[w]) or range(nc):
            e_src.append(w * nc + c)
            e_dst.append(dram)
            e_vol.append(mean_vol * 0.1)
            paths.append([])
        for c in sorted(active[w + 1]) or range(nc):
            e_src.append(dram)
            e_dst.append((w + 1) * nc + c)
            e_vol.append(mean_vol * 0.1)
            paths.append([])
    e_src = np.asarray(e_src, dtype=np.int64)
    e_dst = np.asarray(e_dst, dtype=np.int64)
    e_vol = np.asarray(e_vol, dtype=np.float64)
    out_sum = np.bincount(e_src, weights=e_vol, minlength=n_nodes)
    w_e = e_vol / np.maximum(out_sum[e_src], 1e-300)
    s0 = np.zeros(n_nodes)
    for (c, w), (prob, _) in core_c.items():
        s0[w * nc + c] = max(s0[w * nc + c], prob)
    link_prob = np.zeros((n_windows, mesh.n_links))
    for (li, w), (prob, _) in link_c.items():
        link_prob[w, li] = max(link_prob[w, li], prob)
    win_e = np.minimum(e_src // nc, n_windows - 1)
    l0 = np.array([float(link_prob[win_e[i], p].max()) if p else 0.0
                   for i, p in enumerate(paths)])
    if not len(e_src):
        return s0.copy(), np.zeros(mesh.n_links)
    rnd = _bf16_array if lowp else (lambda x: x)
    s, l, w_e = rnd(s0), rnd(l0), rnd(w_e)
    for _ in range(max_iters):
        s_new = rnd((1.0 - lam) * s0 + lam * np.bincount(
            e_dst, weights=w_e * s[e_src], minlength=n_nodes))
        l_new = rnd(alpha * w_e + beta * s[e_src] + gamma * l)
        delta = np.abs(s_new - s).sum() + np.abs(l_new - l).sum()
        s, l = s_new, l_new
        if delta < eps:
            break
    link_scores = np.zeros(mesh.n_links)
    for i, path in enumerate(paths):
        if not path:
            continue
        th = theta[int(min(e_src[i] // nc, n_windows - 1)), path]
        share = th / max(th.sum(), 1e-300)
        for lid, sh in zip(path, share):
            link_scores[lid] = max(link_scores[lid], l[i] * sh)
    return s, link_scores


# ---------------------------------------------------------------------------
# the verdict
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Reading:
    """What the reference reads off one verdict, for the comparison."""
    comp: list[Pattern]
    comm: list[Pattern]
    sketch_bytes: int
    core_z: np.ndarray        # [cores] largest z of each core's candidates
    theta: np.ndarray         # [windows, links]
    observed: np.ndarray
    failrank: np.ndarray | None   # raw node scores, None if not reached
    verdict: tuple            # (flagged, kind, location)
    severity: float


def verdict(stream: Stream, mesh: Mesh, threshold: float,
            lowp: bool = False) -> Reading:
    comp, comm = stream.comp.patterns(), stream.comm.patterns()
    total = stream.elapsed
    core_c = detect_cores(comp, total)
    theta, observed, link_c = detect_links(comm, mesh, total)
    core_ev, core_z = np.zeros(mesh.n_cores), np.zeros(mesh.n_cores)
    for (c, _), (prob, z) in core_c.items():
        core_ev[c] = max(core_ev[c], prob)
        core_z[c] = max(core_z[c], z)
    link_ev, link_z = np.zeros(mesh.n_links), np.zeros(mesh.n_links)
    for (li, _), (prob, r) in link_c.items():
        link_ev[li] = max(link_ev[li], prob)
        link_z[li] = max(link_z[li], r)
    reading = Reading(comp, comm, stream.sketch_bytes(), core_z, theta,
                      observed, None, (False, None, None), 0.0)
    if max(core_ev.max(), link_ev.max()) < threshold:
        return reading
    s, link_fr = failrank_scores(comm, mesh, total, core_c, link_c, theta,
                                 lowp=lowp)
    reading.failrank = s
    nc = mesh.n_cores
    core_fr = s[:4 * nc].reshape(4, nc).max(axis=0)
    core_fr = core_fr / max(core_fr.max(), 1e-12)
    link_fr = link_fr / max(link_fr.max(), 1e-12)
    core_sc = core_ev * (0.5 + core_fr)
    link_sc = link_ev * (0.5 + link_fr)
    if core_sc.max() >= link_sc.max():
        c = int(np.argmax(core_sc))
        reading.verdict, reading.severity = (True, "core", c), float(core_z[c])
    else:
        li = int(np.argmax(link_sc))
        reading.verdict = (True, "link", li)
        reading.severity = float(link_z[li])
    return reading
