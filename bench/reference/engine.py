"""The engine cells' reference: a plain, frozen model of what the DSE engine
computes for one design point, importing nothing of the program.

Given the point's knobs, the configuration (the simulated system's device
and links) and the traffic (the scenario's parameters), it resolves the
network and the parallel groups, prices every op of the trace (a roofline
for compute, an alpha-beta model for collectives), schedules the trace with
a discrete-event loop in float64 on the host, and finalises the point as the
scenario does: the memory gate, the statistics and the reward.

The trace is the one input it takes from the program: the op list that the
timed path simulated, reduced to plain values, and accepted only if its
SHA-256 is the one the traffic file pins (``trace_sha256``).  Any change to
the trace fails the comparison instead of moving both sides together.
"""
from __future__ import annotations

import hashlib
import heapq
import json
import math
from dataclasses import dataclass
from typing import Any

import numpy as np

# per-NPU links an algorithm drives at once, [topology kind][algorithm];
# None: Direct on a fully connected dim drives all n - 1
LINKS = {"ring": {"ring": 1.0, "direct": 1.0, "rhd": 1.0, "dbt": 2.0},
         "switch": {"ring": 1.0, "direct": 1.0, "rhd": 1.0, "dbt": 1.0},
         "fc": {"ring": 1.0, "direct": None, "rhd": 1.0, "dbt": 2.0}}
BYTES_PARAM, BYTES_OPT, BYTES_ACT = 2, 12, 2   # bf16 weights, fp32 master + Adam
TRAIN_MICROBATCHES = 8
REMAT_ACT_LAYERS = 1.5      # residual streams a layer keeps under remat


@dataclass(frozen=True)
class Dim:
    kind: str
    npus: int
    bw: float           # GB/s a link
    lat: float          # us a hop


@dataclass(frozen=True)
class Par:
    n: int
    dp: int
    sp: int
    pp: int
    ws: bool = False

    @property
    def tp(self) -> int:
        return max(self.n // (self.dp * self.sp * self.pp), 1)

    def valid(self) -> bool:
        k = self.dp * self.sp * self.pp
        return k <= self.n and self.n % k == 0


@dataclass
class Eval:
    reward: float
    latency_ms: float
    valid: bool
    detail: dict


# ---------------------------------------------------------------------------
# the trace, as plain values
# ---------------------------------------------------------------------------

def canonical(trace) -> dict:
    """The trace's ops and wave marks as plain values: per op (kind, deps,
    flops, bytes, collective, payload, group, pool, repeat, delay)."""
    ops = [(o.kind, [int(d) for d in o.deps], float(o.flops), float(o.bytes),
            o.coll, float(o.size_bytes), o.group, int(o.pool), int(o.repeat),
            float(o.delay_us)) for o in trace.ops]
    marks = [[[int(u) for u in t] for t in m["seg_tails"]]
             for m in trace.meta.get("wave_marks", [])]
    return {"ops": ops, "wave_marks": marks}


def digest(canon: dict) -> str:
    return hashlib.sha256(json.dumps(canon).encode()).hexdigest()


class Plan:
    """Resources and dependencies of the trace: one compute stream per
    pool, one engine per (pool, group), one shared transfer engine, and a
    private timer for each delay."""

    def __init__(self, canon: dict) -> None:
        ops = canon["ops"]
        self.n = len(ops)
        self.marks = canon["wave_marks"]
        self.res_name, self.res_pool = ["compute"], [0]
        index = {(0, "compute"): 0}
        self.res_of, self.ndeps = [0] * self.n, [0] * self.n
        self.children: list[list[int]] = [[] for _ in range(self.n)]
        self.roots, self.pools = [], {0}
        self.ops = ops
        for uid, (kind, deps, _, _, _, _, group, pool, _, _) in enumerate(ops):
            self.pools.add(pool)
            if kind == "comp":
                key = (pool, "compute")
            elif kind == "delay":
                key = (pool, f"_delay{uid}")
            else:
                key = (0 if group == "xfer" else pool, group)
            if key not in index:
                index[key] = len(self.res_name)
                self.res_name.append(key[1])
                self.res_pool.append(key[0])
            self.res_of[uid] = index[key]
            self.ndeps[uid] = len(deps)
            if not deps:
                self.roots.append(uid)
            for d in deps:
                self.children[d].append(uid)
        self.pools = sorted(self.pools)


# ---------------------------------------------------------------------------
# the network and the parallel groups
# ---------------------------------------------------------------------------

def carve(dims: tuple[Dim, ...], caps: list[int], need: int) -> list[tuple[int, Dim]]:
    """Take ``need`` NPUs from the dims innermost first, by common factors
    of what each dim has left; what no dim covers becomes a virtual dim at
    the outermost dim's speed.  Each carved dim keeps its source index."""
    out = []
    for i, d in enumerate(dims):
        if need <= 1:
            break
        if caps[i] <= 1:
            continue
        take = math.gcd(need, caps[i])
        if take <= 1:
            continue
        out.append((i, Dim(d.kind, take, d.bw, d.lat)))
        caps[i] //= take
        need //= take
    if need > 1 and dims:
        last = dims[-1]
        out.append((len(dims) - 1, Dim(last.kind, need, last.bw, last.lat)))
    return out


def subnet(dims: tuple[Dim, ...], n: int) -> tuple[tuple[Dim, ...], tuple[int, ...]]:
    carved = carve(dims, [d.npus for d in dims], n)
    return tuple(d for _, d in carved), tuple(i for i, _ in carved)


def group_dims(dims: tuple[Dim, ...], par: Par) -> dict[str, list[tuple[int, Dim]]]:
    """Groups onto dims, innermost first: tp, sp, dp, pp; experts ride tp."""
    caps = [d.npus for d in dims]
    out = {g: carve(dims, caps, n) for g, n in
           (("tp", par.tp), ("sp", par.sp), ("dp", par.dp), ("pp", par.pp))}
    out["ep"] = out["tp"]
    return out


# ---------------------------------------------------------------------------
# pricing
# ---------------------------------------------------------------------------

def coll_time_us(kind: str, size: float, d: Dim, algo: str, chunks: int) -> float:
    n = d.npus
    if n <= 1 or size <= 0:
        return 0.0
    lg = math.ceil(math.log2(n))
    per_pass = {"ring": n - 1, "direct": 1.0}.get(algo, lg)
    if kind == "all_reduce":
        steps = 2.0 * per_pass
    elif kind == "all_to_all":
        steps = 1.0 if algo == "direct" else per_pass
    else:
        steps = float(per_pass)
    steps *= max(chunks, 1)
    wire = (2.0 if kind == "all_reduce" else 1.0) * size * ((n - 1) / n)
    links = LINKS[d.kind][algo]
    links = float(n - 1) if links is None else links
    cong = 1.0
    if n > 2 and d.kind == "ring":
        if algo == "direct":
            cong = n / 4.0
        elif algo == "rhd":
            cong = max(1.0, (n / 2.0) / lg)
        elif algo == "dbt":
            cong = max(1.0, n / (2.0 * lg))
    return steps * d.lat + (wire / (d.bw * links / cong)) * 1e-3


def multidim_time_us(kind: str, size: float, dims: tuple[Dim, ...],
                     algos: tuple[str, ...], chunks: int, mode: str) -> float:
    """Hierarchical: reduce-scatter up the dims and all-gather back, payload
    shrinking by each dim, chunks pipelining the phases; BlueConnect runs
    the per-dim phases concurrently on disjoint chunks."""
    idx = [i for i in range(len(dims)) if dims[i].npus > 1]
    if not idx or size <= 0:
        return 0.0
    if len(idx) == 1:
        return coll_time_us(kind, size, dims[idx[0]], algos[idx[0]], chunks)
    phases = []
    if kind == "all_to_all":
        phases = [coll_time_us(kind, size, dims[i], algos[i], chunks) for i in idx]
    else:
        scale = 1.0
        for i in idx:
            d = dims[i]
            if kind == "all_reduce":
                phases.append(
                    coll_time_us("reduce_scatter", size * scale, d, algos[i], chunks)
                    + coll_time_us("all_gather", size * scale, d, algos[i], chunks))
            else:
                phases.append(coll_time_us(kind, size * scale, d, algos[i], chunks))
            scale /= d.npus
    c = max(chunks, 1)
    total = 0.0
    if mode == "blueconnect":
        for p in phases:
            total += p
        return max(phases) + (total - max(phases)) / c
    for p in phases:
        total += p / c
    return total + (c - 1) / c * max(phases)


class Point:
    """One design point's system: the network its knobs build, the device
    and links of the configuration."""

    def __init__(self, config: dict, point: dict) -> None:
        sysc = config["system"]
        self.dims = tuple(Dim(k, int(n), float(b), float(sysc["link_latency_us"]))
                          for k, n, b in zip(point["topology"], point["npus_per_dim"],
                                             point["bw_per_dim"]))
        self.peak = sysc["device"]["peak_tflops"] * 1e12
        self.membw = sysc["device"]["mem_bw_gbps"] * 1e9
        self.xfer_lat = float(sysc["xfer_latency_us"])
        self.algo = tuple(point["coll_algo"])
        self.chunks = int(point["chunks"])
        self.policy = point["sched_policy"]
        self.mode = point["multidim_coll"]

    def group_net(self, carved):
        if not carved:
            return None
        n = len(self.algo)
        return (tuple(d for _, d in carved),
                tuple(self.algo[min(i, n - 1)] if n else "ring" for i, _ in carved))

    def durations(self, plan: Plan, pools: dict[int, tuple]) -> list[float]:
        """Each op's duration; ``pools`` maps a pool to (Par, dims, dim
        index map or None)."""
        nets = {}
        for p in plan.pools:
            par, dims, dim_map = pools[p]
            for g, carved in group_dims(dims, par).items():
                if dim_map:
                    last = len(dim_map) - 1
                    carved = [(dim_map[min(i, last)], d) for i, d in carved]
                nets[(p, g)] = self.group_net(carved)
        priced: dict[tuple, float] = {}
        out = []
        for kind, _, fl, by, coll, size, group, pool, rep, delay in plan.ops:
            if kind == "comp":
                out.append(max(fl * rep / self.peak, by * rep / self.membw) * 1e6)
            elif kind == "delay":
                out.append(delay)
            else:
                key = (pool, group, coll, size)
                if key not in priced:
                    if group == "xfer":
                        t = self.xfer_lat + (size / self.dims[-1].bw) * 1e-3
                    elif nets.get((pool, group)) is None:
                        t = 0.0
                    else:
                        sub, algos = nets[(pool, group)]
                        t = multidim_time_us(coll, size, sub, algos,
                                             self.chunks, self.mode)
                    priced[key] = t
                out.append(priced[key] * rep)
        return out

    def bw_sum(self) -> float:
        total = 0.0
        for d in self.dims:
            total += d.bw
        return total


# ---------------------------------------------------------------------------
# scheduling
# ---------------------------------------------------------------------------

def schedule(plan: Plan, dur: list[float], policy: str):
    """Ready ops queue on their resource, LIFO or FIFO by when they became
    ready; an op starts when its resource is free.  Returns the makespan,
    each resource's busy time and each op's finish time (us)."""
    ndeps = list(plan.ndeps)
    queues: list[list[tuple[int, int]]] = [[] for _ in plan.res_name]
    free_at = [0.0] * len(plan.res_name)
    busy = [0.0] * len(plan.res_name)
    sign = -1 if policy == "lifo" else 1
    events: list[tuple[float, int, int]] = []
    finish = [0.0] * plan.n
    seq = eseq = done = 0
    makespan = 0.0

    def start(r: int, now: float) -> None:
        nonlocal eseq
        _, uid = heapq.heappop(queues[r])
        free_at[r] = now + dur[uid]
        busy[r] += dur[uid]
        eseq += 1
        heapq.heappush(events, (now + dur[uid], eseq, uid))

    for uid in plan.roots:
        seq += 1
        heapq.heappush(queues[plan.res_of[uid]], (sign * seq, uid))
    for r in range(len(queues)):
        if queues[r]:
            start(r, 0.0)
    while events:
        now, _, uid = heapq.heappop(events)
        done += 1
        finish[uid] = now
        makespan = max(makespan, now)
        touched = [plan.res_of[uid]]
        for ch in plan.children[uid]:
            ndeps[ch] -= 1
            if ndeps[ch] == 0:
                seq += 1
                r = plan.res_of[ch]
                heapq.heappush(queues[r], (sign * seq, ch))
                if r not in touched:
                    touched.append(r)
        for r in touched:
            if free_at[r] <= now and queues[r]:
                start(r, now)
    if done != plan.n:
        raise RuntimeError(f"deadlock: {done}/{plan.n} ops finished")
    return makespan, busy, finish


def busy_summary(plan: Plan, makespan: float, busy: list[float]):
    """(pool 0's compute time, each group's engine time, the time covered
    by no compute stream)."""
    compute = {plan.res_pool[r]: busy[r] for r, name in enumerate(plan.res_name)
               if name == "compute"}
    comm: dict[str, float] = {}
    for r, name in enumerate(plan.res_name):
        if name == "compute" or name.startswith("_delay"):
            continue
        key = name if plan.res_pool[r] == 0 else f"{name}@p{plan.res_pool[r]}"
        comm[key] = comm.get(key, 0.0) + busy[r]
    covered = 0.0
    for v in compute.values():
        covered += v
    return compute.get(0, 0.0), comm, max(0.0, makespan - covered)


# ---------------------------------------------------------------------------
# the memory gate
# ---------------------------------------------------------------------------

def param_count(m: dict) -> int:
    d, h, kv = m["hidden_size"], m["num_attention_heads"], m["num_key_value_heads"]
    hd, ff = d // h, m["intermediate_size"]
    layer = d + d * h * hd + 2 * d * kv * hd + h * hd * d + d + 3 * d * ff
    if m["qkv_bias"]:
        layer += (h + 2 * kv) * hd
    embed = m["vocab_size"] * d * (1 if m["tie_word_embeddings"] else 2)
    return embed + d + m["num_hidden_layers"] * layer


def kv_bytes(m: dict, batch: float, seq: int, tp: int = 1) -> float:
    hd = m["hidden_size"] // m["num_attention_heads"]
    return (m["num_hidden_layers"] * batch * seq * m["num_key_value_heads"]
            * hd * 2 * BYTES_ACT / tp)


def footprint(m: dict, par: Par, batch: int, seq: int, train: bool) -> tuple[float, float]:
    """(total GB a NPU, of which KV cache GB): weights sharded over tp, pp
    and (weight-sharded) dp; Adam state in training; activations of one
    microbatch (training, remat) or of the pass; the KV cache when serving."""
    p, tp = param_count(m), par.tp
    params = p * BYTES_PARAM / (tp * par.pp * (par.dp if par.ws else 1))
    opt = 0.0
    if train:
        opt = p * BYTES_OPT / (tp * par.pp * (par.dp if par.ws else 1))
    b = batch / par.dp / (TRAIN_MICROBATCHES if train else 1)
    per_layer = b * (seq / par.sp) * m["hidden_size"] * BYTES_ACT
    if train:
        acts = per_layer * max(1, m["num_hidden_layers"] // par.pp) * REMAT_ACT_LAYERS
        kv = 0.0
    else:
        acts, kv = per_layer * 2, kv_bytes(m, b, seq, tp)
    return params / 1e9 + opt / 1e9 + acts / 1e9 + kv / 1e9, kv / 1e9


def invalid(why: str) -> Eval:
    return Eval(0.0, float("inf"), False, {"why": why})


# ---------------------------------------------------------------------------
# scenarios
# ---------------------------------------------------------------------------

class Reference:
    """Evaluates design points of one cell against its pinned trace."""

    def __init__(self, config: dict, traffic: dict, canon: dict) -> None:
        self.config, self.traffic = config, traffic
        self.scn = traffic["scenario"]
        self.plan = Plan(canon)
        self.capacity = float(config["system"]["device"]["capacity_gb"])
        self.n_npus = int(config["system"]["n_npus"])

    def evaluate(self, point: dict) -> Eval:
        for k, v in self.traffic["pinned"].items():
            if point[k] != (tuple(v) if isinstance(v, list) else v):
                raise ValueError(f"{k} is pinned to {v} in the traffic; the "
                                 f"trace is that of the pinned knobs only")
        kind = self.scn["type"]
        if kind == "train":
            return self.train(point)
        if kind == "request_stream":
            return self.stream(point)
        raise ValueError(f"no reference for scenario type {kind!r}")

    def par(self, point: dict, n: int) -> Par:
        return Par(n, point["dp"], point["sp"], point["pp"], bool(point["weight_sharded"]))

    def train(self, point: dict) -> Eval:
        if self.scn.get("mode", "train") != "train":
            raise ValueError("the reference prices training steps only")
        par = self.par(point, self.n_npus)
        if not par.valid():
            return invalid("parallelization invalid")
        fp, _ = footprint(self.config, par, self.scn["batch"], self.scn["seq"], True)
        if fp > self.capacity:
            return invalid(f"memory {fp:.1f}GB > {self.capacity}GB")
        sysp = Point(self.config, point)
        dur = sysp.durations(self.plan, {p: (par, sysp.dims, None)
                                         for p in self.plan.pools})
        makespan, busy, _ = schedule(self.plan, dur, sysp.policy)
        compute, comm, exposed = busy_summary(self.plan, makespan, busy)
        latency = makespan / 1e3
        x = latency * sysp.bw_sum() - 1.0
        return Eval(1.0 / math.sqrt(x * x + 1e-12), latency, True, {
            "footprint_gb": fp, "exposed_comm_us": exposed,
            "compute_busy_us": compute, "comm_busy_us": comm})

    # -- request stream: arrivals, admission waves, two pools ----------------
    def arrivals(self) -> list[float]:
        rng = np.random.default_rng(self.scn["seed"])
        t, out = 0.0, []
        for g in rng.exponential(1000.0 / self.scn["rate_rps"],
                                 self.scn["n_requests"]).tolist():
            t += g
            out.append(t)
        return out

    @staticmethod
    def waves(arrivals: list[float], window: float, cap: int) -> list[tuple[list[int], float]]:
        """A wave opens at its first request and is released ``window`` ms
        later or the moment it holds ``cap`` requests."""
        waves, cur, deadline = [], [], 0.0
        for i, t in enumerate(arrivals):
            if cur and t > deadline:
                waves.append((cur, deadline))
                cur = []
            cur.append(i)
            if len(cur) == 1:
                deadline = t + window
            if len(cur) == cap:
                waves.append((cur, t))
                cur = []
        if cur:
            waves.append((cur, deadline))
        return waves

    def stream(self, point: dict) -> Eval:
        s = self.scn
        if s.get("prompt_len_range") or s.get("decode_len_range"):
            raise ValueError("the reference prices homogeneous requests only")
        seq, dec, max_batch = s["seq"], s["decode_tokens"], s["max_batch"]
        n_pre = int(round(float(point["prefill_frac"]) * self.n_npus))
        n_dec = self.n_npus - n_pre
        if n_pre < 1 or n_dec < 1:
            return invalid(f"degenerate pool split {n_pre}/{n_dec}")
        par_pre = self.par(point, n_pre)
        decode_batch = int(point["decode_batch"])
        replicas = min(n_dec, max(1, math.ceil(max_batch / decode_batch)))
        par_dec = Par(replicas * (n_dec // replicas), replicas, 1, 1)
        resident = min(decode_batch * replicas, max_batch)
        if not par_pre.valid():
            return invalid(f"prefill parallelization invalid on {n_pre} NPUs")
        fp_pre, _ = footprint(self.config, par_pre, max_batch, seq, False)
        if fp_pre > self.capacity:
            return invalid(f"prefill memory {fp_pre:.1f}GB > {self.capacity}GB")
        fp_dec, _ = footprint(self.config, par_dec, resident, seq, False)
        if fp_dec > self.capacity:
            return invalid(f"decode memory {fp_dec:.1f}GB > {self.capacity}GB")

        arrivals = self.arrivals()
        waves = self.waves(arrivals, float(point["batch_window_ms"]), max(1, resident))
        if len(waves) != len(self.plan.marks):
            raise ValueError(f"{len(waves)} waves, the trace has {len(self.plan.marks)}")
        sysp = Point(self.config, point)
        pools = {0: (par_pre, *subnet(sysp.dims, par_pre.n)),
                 1: (par_dec, *subnet(sysp.dims, par_dec.n))}
        dur = sysp.durations(self.plan, pools)
        makespan, _, finish = schedule(self.plan, dur, sysp.policy)

        ttft, tpot, lat = [], [], []
        for (idxs, _), tails in zip(waves, self.plan.marks):
            first = max(finish[u] for u in tails[1]) / 1e3
            last = max(finish[u] for u in tails[-1]) / 1e3
            per_token = (last - first) / max(dec - 1, 1)
            for i in idxs:
                ttft.append(first - arrivals[i])
                tpot.append(per_token)
                lat.append(last - arrivals[i])
        latency = makespan / 1e3
        horizon = max(latency, arrivals[-1])
        ok = sum(1 for a, b in zip(ttft, tpot)
                 if a <= s["ttft_slo_ms"] and b <= s["tpot_slo_ms"])
        goodput = ok / max(horizon / 1e3, 1e-9)
        p99 = float(np.percentile(lat, 99))
        return Eval(goodput, p99, True, {
            "scenario": "request-stream", "prefill_npus": par_pre.n,
            "decode_npus": par_dec.n, "decode_tp": par_dec.tp,
            "decode_replicas": par_dec.dp, "decode_batch": decode_batch,
            "batch_window_ms": float(point["batch_window_ms"]),
            "max_inflight": int(point["max_inflight"]),
            "waves": len(waves), "wave_sizes": [len(i) for i, _ in waves],
            "prefill_gb": fp_pre, "decode_gb": fp_dec,
            "makespan_ms": latency,
            "n_requests": len(ttft), "n_ok": ok,
            "ttft_p50_ms": float(np.percentile(ttft, 50)),
            "ttft_p99_ms": float(np.percentile(ttft, 99)),
            "tpot_p50_ms": float(np.percentile(tpot, 50)),
            "tpot_p99_ms": float(np.percentile(tpot, 99)),
            "latency_p99_ms": p99, "goodput_rps": goodput, "horizon_ms": horizon})
