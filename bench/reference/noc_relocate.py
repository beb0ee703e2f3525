"""The plain reference of the relocation deployment, written from its semantics.

`simulate` runs sweep points of the package of `bench/reference/noc.py`
under what a SHIFT-style deployment adds to it: demand that shifts epoch by
epoch, interposer and telemetry faults, compute relocation gated by the KF,
the guarded KF and the control levers.  Like `noc.py`, whose model it copies
and extends, it imports nothing of the program under test (only `noc.py`'s
constants, `package` and `network`), keeps every FIFO in plain int32
arrays, draws each cycle's randomness from its own key and runs on the host
CPU.

What it adds to `noc.py`'s model (arXiv:2606.28754 for the relocation,
DESIGN.md §12, §16, §17 for how the simulator documents the rest):

- Demand: a point's scenario is a list of segments.  Epoch e takes the rates
  of the last segment whose start, as a fraction of the run, rounds to at
  most e (`round(start * n_epochs)`, Python's rounding); a segment with
  `ramp_to` moves its rates linearly to those of `ramp_to` over its epochs,
  in float32; `pin_phase` 1 (0) holds the burst phase high (low) by
  entering (leaving) it with probability 1 every cycle.
- Faults: each event covers epochs [round(start E), round(stop E)); with a
  `period` p > 0 only the epochs of the first, third, ... runs of p epochs
  from its start.  `link` takes the listed mesh output ports of the listed
  routers (all of them where a list is empty) out of service, and the link's
  other direction with them: a head packet routed through it is never
  granted and waits in its FIFO.  `router` (a brownout) grants nothing at
  the router, ejection included.  `telem` replaces the epoch's normalised observations by -1 (mode 1), adds
  `mag` to them (mode 2) or makes them NaN (mode 3).  A later event wins
  where two set the same epoch's telemetry.
- Placement: two class plans per epoch, the base plan and the boosted one.
  The configuration's event writes its plan into one of them over its
  window; outside it both are the package's own layout.  `gpu_near_mc` puts
  the GPU class on the non-MC tiles nearest an MC by Manhattan distance
  (ties to the lower router id) and the CPU class on the rest: class counts
  stay as they were and MC tiles never move.  While the applied
  configuration is boosted and the point's control includes placement, the
  tiles take the boosted plan's classes: a tile's generation rate, its
  request subnet, the class of the requests it sends and the GPU counters
  it feeds follow its class of the epoch.  A packet keeps the class it was
  sent with.
- Control: `bandwidth` lets the applied configuration set the VC split and
  the switch priority (the paper's controller), `placement` lets it choose
  the class plan only, `joint` both.
- The guarded KF (the closed form of `noc.py`, state x, variance P, gain
  P^/(3P^ + r)): the normalised innovation squared nu^T S^-1 nu of the
  epoch, nu = z - x^, uses the closed-form S = r I + P^ 1 1^T, inverted by
  Sherman-Morrison.  An epoch whose observation is not finite or whose NIS
  exceeds the threshold is rejected: the filter keeps its prior (x^, P^).
  A run of `watchdog_limit` rejections or a variance that is not finite or
  above `cov_limit` marks it unhealthy; the epoch the run reaches the limit,
  or the variance goes bad, P resets to 1 (and a non-finite x to 0).  While
  unhealthy the applied configuration falls back to the fair split and the
  boost timer clears; the hold timer is kept.

`lowp=True` is the control: the epoch layer computed in bfloat16, the
precision below the configuration's float32.
"""
from __future__ import annotations

import functools
import os
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.noc import (ACROSS, COUNTERS, CPU, CPU_LAT_SENSITIVITY,
                                 CPU_NOLOAD_LAT, FIELDS, GPU, GPU_BASE_IPC,
                                 HOLD, LOCAL, MC, PORTS, REVERT, SOURCE_QUEUE,
                                 WARMUP, WORKLOAD, network, package)

NORTH, EAST, SOUTH, WEST = range(4)
MESH_PORTS = (NORTH, EAST, SOUTH, WEST)
TELEM_DROP, TELEM_SPIKE, TELEM_NAN = 1, 2, 3
FAULT_KINDS = ("link", "router", "telem")
CONTROLS = {"bandwidth": (True, False), "placement": (False, True),
            "joint": (True, True)}       # (bandwidth lever, placement lever)


# ---- the per-epoch streams, on the host


def demand_rows(segments: list[dict], n_epochs: int) -> np.ndarray:
    """(n_epochs, 5) float32 rates of `noc.WORKLOAD`, epoch by epoch."""
    starts = [s["start"] for s in segments]
    if not segments or starts[0] != 0.0 or starts != sorted(starts):
        raise ValueError(f"segment starts must begin at 0 and rise: {starts}")
    bounds = [int(round(s * n_epochs)) for s in starts] + [n_epochs]
    rows = np.zeros((n_epochs, len(WORKLOAD)), np.float32)
    for seg, lo, hi in zip(segments, bounds, bounds[1:]):
        if hi <= lo:
            continue
        a = np.float32([seg["profile"][k] for k in WORKLOAD])
        if seg.get("ramp_to") is not None:
            b = np.float32([seg["ramp_to"][k] for k in WORKLOAD])
            t = np.arange(hi - lo, dtype=np.float32) / max(hi - lo - 1, 1)
            rows[lo:hi] = a + t[:, None] * (b - a)
        else:
            rows[lo:hi] = a
        pin = seg.get("pin_phase")
        if pin is not None:
            rows[lo:hi, 2] = 1.0 if pin == 1 else 0.0      # p_enter
            rows[lo:hi, 3] = 0.0 if pin == 1 else 1.0      # p_exit
    return rows


def event_epochs(event: dict, n_epochs: int) -> np.ndarray:
    """The epochs a fault or placement event covers."""
    lo = int(round(event["start"] * n_epochs))
    hi = int(round(event["stop"] * n_epochs))
    epochs = np.arange(lo, hi)
    period = event.get("period", 0)
    if period > 0:
        epochs = epochs[((epochs - lo) // period) % 2 == 0]
    return epochs


def fault_rows(events: list[dict], n_epochs: int, neighbour: np.ndarray):
    """(link_ok (E, R, PORTS), router_ok (E, R), telemetry mode (E,), spike
    magnitude (E,))."""
    R = neighbour.shape[0]
    link_ok = np.ones((n_epochs, R, PORTS), bool)
    router_ok = np.ones((n_epochs, R), bool)
    telem = np.zeros(n_epochs, np.int32)
    mag = np.zeros(n_epochs, np.float32)
    for ev in events:
        if ev["kind"] not in FAULT_KINDS:
            raise ValueError(f"unknown fault kind {ev['kind']!r}")
        e = event_epochs(ev, n_epochs)
        routers = list(ev.get("routers") or range(R))
        if ev["kind"] == "telem":
            telem[e] = ev["mode"]
            mag[e] = np.float32(ev.get("mag", 0.0))
        elif ev["kind"] == "router":
            router_ok[np.ix_(e, routers)] = False
        else:
            for r in routers:
                for p in ev.get("ports") or MESH_PORTS:
                    if p not in MESH_PORTS:
                        raise ValueError(f"port {p} is not a mesh port")
                    link_ok[e, r, p] = False
                    if neighbour[r, p] >= 0:         # both directions
                        link_ok[e, neighbour[r, p], ACROSS[p]] = False
    return link_ok, router_ok, telem, mag


def plan(name: str, width: int, kind: np.ndarray, mcs: np.ndarray):
    """A layout of the tiles' classes; MC tiles keep theirs."""
    if name != "gpu_near_mc":
        raise ValueError(f"unknown placement plan {name!r}")
    ids = np.arange(len(kind))
    x, y = ids % width, ids // width
    dist = (np.abs(x[:, None] - x[mcs][None]) +
            np.abs(y[:, None] - y[mcs][None])).min(1)
    tiles = sorted((r for r in ids if kind[r] != MC),
                   key=lambda r: (dist[r], r))
    n_gpu = int((kind == GPU).sum())
    out = kind.copy()
    out[tiles[:n_gpu]] = GPU
    out[tiles[n_gpu:]] = CPU
    return out


def placement_rows(event: dict, n_epochs: int, width: int,
                   kind: np.ndarray, mcs: np.ndarray):
    """(base plan (E, R), boosted plan (E, R)) int32."""
    base = np.tile(kind, (n_epochs, 1)).astype(np.int32)
    boost = base.copy()
    lo = int(round(event["start"] * n_epochs))
    hi = int(round(event["stop"] * n_epochs))
    target = boost if event["slot"] == "boost" else base
    target[lo:hi] = plan(event["plan"], width, kind, mcs)
    return base, boost


# ---- the model


def _simulate(cfg: dict, mode: str, lowp: bool, demand, link_ok, router_ok,
              telem, mag, cls0, cls1, levers, seed):
    route, neighbour, kind, mcs = package(cfg["width"], cfg["height"],
                                          cfg["n_mc"])
    n_sub, V, split, kf_drives = network(mode, cfg["n_vcs"])
    R, B, Q = len(kind), cfg["buf_depth"], cfg["mc_queue_cap"]
    E, L = cfg["n_epochs"], cfg["epoch_len"]
    i32, f32 = jnp.int32, jnp.float32
    rnd = ((lambda x: jax.lax.reduce_precision(x, 8, 7)) if lowp
           else (lambda x: x))
    bw_enable, place_enable, guard = levers[0], levers[1], levers[2]

    route, neighbour = jnp.asarray(route), jnp.asarray(neighbour)
    mcs, rid = jnp.asarray(mcs), jnp.arange(R)
    is_mc = jnp.asarray(kind == MC)
    if n_sub == 4:      # subnet 2c carries class c's requests, 2c+1 replies
        request_sub = lambda c: 2 * c
        reply_sub = lambda c: 2 * c + 1
    else:
        request_sub = lambda c: jnp.zeros_like(c)
        reply_sub = lambda c: jnp.ones_like(c)
    is_request = np.isin(np.arange(n_sub), [0, 2])      # (n_sub,)
    n_request = int(is_request.sum())
    gpu_vcs = jnp.asarray(np.stack([split[0][0], split[1][0]]))
    cpu_vcs = jnp.asarray(np.stack([split[0][1], split[1][1]]))
    sub_ix = jnp.arange(n_sub)[:, None, None]

    def first_free(counts, allowed):
        """The first VC with room that the class may use, and whether any."""
        room = (counts < B) & allowed
        return room.any(-1), jnp.argmax(room, -1)

    def push(buf, s, r, p, v, ok, packet):
        """Append a packet to FIFO (s, r, p, v) wherever `ok`."""
        tail = (buf["head"][s, r, p, v] + buf["count"][s, r, p, v]) % B
        s = jnp.where(ok, s, n_sub)            # past the end: no write
        out = dict(buf)
        for f in FIELDS:
            out[f] = buf[f].at[s, r, p, v, tail].set(packet[f], mode="drop")
        out["count"] = buf["count"].at[s, r, p, v].add(1, mode="drop")
        return out

    def inject(buf, want, sub, packet, masks):
        free, vc = first_free(buf["count"][sub, rid, LOCAL],
                              masks[packet["cls"]])
        ok = want & free
        return push(buf, sub, rid, LOCAL, vc, ok, packet), ok

    def switch(buf, accept, active, favour, masks, links, granting):
        """Step 4 on every router of every subnet; returns the new FIFOs and
        what left through the Local outputs.  `links` (R, PORTS) says which
        links are in service, `granting` (R,) which routers grant at all."""
        PV = PORTS * V
        h = buf["head"][..., None]
        head = {f: jnp.take_along_axis(buf[f], h, -1)[..., 0].reshape(
            n_sub, R, PV) for f in FIELDS}
        valid = (buf["count"] > 0).reshape(n_sub, R, PV)
        wants_port = route[rid[None, :, None], head["dest"]]     # (S, R, PV)
        o = jnp.arange(PORTS)
        req = valid[:, :, None] & (wants_port[:, :, None] == o[:, None])
        later = jnp.where((favour < 0) | (head["cls"] == favour), 0, PV)
        order = (jnp.arange(PV) - buf["rr"][..., None]) % PV
        order = jnp.where(req, order + later[:, :, None], 2 * PV)
        win = jnp.argmin(order, -1)                              # (S, R, O)
        wanted = req.any(-1)
        won = {f: jnp.take_along_axis(head[f], win, -1) for f in FIELDS}

        nb = jnp.maximum(neighbour, 0)
        down = buf["count"][sub_ix, nb[None], ACROSS[None, None]]  # (S,R,O,V)
        room, down_vc = first_free(down, masks[won["cls"]])
        sink = o == LOCAL
        fire = (wanted & active[:, None, None] & granting[None, :, None]
                & jnp.where(sink, accept[..., None],
                            (neighbour >= 0) & links & room))
        in_port = win // V
        clash = (fire[..., None, :] & (in_port[..., None, :] == in_port[..., None])
                 & (o[None, :] < o[:, None]))
        fire = fire & ~clash.any(-1)

        r_ix = rid[None, :, None]
        s_fire = jnp.where(fire, sub_ix, n_sub)
        buf = dict(buf)
        buf["head"] = buf["head"].at[s_fire, r_ix, in_port, win % V].add(
            1, mode="drop") % B
        buf["count"] = buf["count"].at[s_fire, r_ix, in_port, win % V].add(
            -1, mode="drop")
        buf["rr"] = jnp.where(fire, (win + 1) % PV, buf["rr"])
        link = fire & ~sink
        buf = push(buf, sub_ix, nb[None], ACROSS[None, None], down_vc, link,
                   won)
        refused = wanted[..., LOCAL] & ~accept
        events = {f: won[f][..., LOCAL] for f in FIELDS}
        events.update(
            eject=fire[..., LOCAL],
            moved=fire.sum(dtype=i32),
            dram_gpu=(refused & (won["cls"][..., LOCAL] == 1)).sum(dtype=i32))
        return buf, events

    def epoch(carry, x):
        buf, mcq, tile, phase, kf, pol = carry
        e, key, rates, links, granting, tmode, tmag, base, boost = x
        boosted = pol["config"] > 0
        bw = boosted & bw_enable
        masks = jnp.stack([cpu_vcs[bw.astype(i32)], gpu_vcs[bw.astype(i32)]])
        favour_on = kf_drives & bw
        # the tiles' classes this epoch: MC tiles are physical
        tile_kind = jnp.where(is_mc, MC, jnp.where(boosted & place_enable,
                                                   boost, base))
        is_gpu, is_cpu = tile_kind == GPU, tile_kind == CPU
        tile_cls = is_gpu.astype(i32)
        lo, hi, p_enter, p_exit, cpu_rate = (rates[i] for i in range(5))

        def cycle(carry, x):
            buf, mcq, tile, phase, c = carry
            t, key = x
            # 1. last cycle's staged replies
            buf, sent = inject(
                buf, mcq["staged"], reply_sub(mcq["st_cls"]),
                dict(dest=mcq["st_dst"], src=rid, cls=mcq["st_cls"],
                     stamp=jnp.full(R, t)), masks)
            staged = mcq["staged"] & ~sent
            # 2. room for request ejections, before service
            room = mcq["count"] <= Q - n_request
            accept = jnp.where(jnp.asarray(is_request)[:, None] & is_mc,
                               room, True)
            # 3. MC service
            serving = is_mc & (mcq["count"] > 0) & ~staged
            timer = jnp.where(serving, jnp.maximum(mcq["timer"] - 1, 0),
                              mcq["timer"])
            done = serving & (timer == 0)
            oldest_src = mcq["src"][rid, mcq["head"]]
            oldest_cls = mcq["cls"][rid, mcq["head"]]
            mcq = dict(
                mcq, staged=staged | done,
                head=jnp.where(done, (mcq["head"] + 1) % Q, mcq["head"]),
                count=mcq["count"] - done,
                timer=jnp.where(done, cfg["mc_service_period"], timer),
                st_dst=jnp.where(done, oldest_src, mcq["st_dst"]),
                st_cls=jnp.where(done, oldest_cls, mcq["st_cls"]))
            # 4. routers
            active = (jnp.arange(n_sub) % 2 == t % 2) if n_sub == 4 else (
                jnp.ones(n_sub, bool))
            favour = jnp.where(favour_on, jnp.asarray([1, 1, 0])[t % 3], -1)
            buf, ev = switch(buf, accept, active, favour, masks, links,
                             granting)
            # 5. requests join their MC's queue
            arrive = ev["eject"] & jnp.asarray(is_request)[:, None] & is_mc
            before = jnp.cumsum(arrive, 0) - arrive
            slot = (mcq["head"] + mcq["count"] + before) % Q
            r_in = jnp.where(arrive, rid, R)
            mcq = dict(mcq, count=mcq["count"] + arrive.sum(0),
                       src=mcq["src"].at[r_in, slot].set(ev["src"],
                                                         mode="drop"),
                       cls=mcq["cls"].at[r_in, slot].set(ev["cls"],
                                                         mode="drop"))
            # 6. replies complete requests
            back = ev["eject"] & ~jnp.asarray(is_request)[:, None] & ~is_mc
            outstanding = tile["outstanding"] - back.sum(0)
            # 7. latency
            lat = jnp.where(ev["eject"], t - ev["stamp"], 0)
            cpu_ej = ev["eject"] & (ev["cls"] == 0)
            gpu_ej = ev["eject"] & (ev["cls"] == 1)
            # 8. generation, at the rates of each tile's class this epoch
            k_phase, k_gen, k_dest = jax.random.split(key, 3)
            u = jax.random.uniform(k_phase, ())
            phase = jnp.where(phase == 0, jnp.where(u < p_enter, 1, 0),
                              jnp.where(u < p_exit, 0, 1))
            rate = jnp.where(is_gpu, jnp.where(phase == 1, hi, lo),
                             jnp.where(is_cpu, cpu_rate, 0.0))
            gen = (jax.random.uniform(k_gen, (R,), f32) < rate) & ~is_mc
            backlog = tile["backlog"] + (gen & (tile["backlog"] < SOURCE_QUEUE))
            # 9. one request per tile, of the tile's class this epoch
            dest = mcs[jax.random.randint(k_dest, (R,), 0, len(mcs))]
            want = (backlog > 0) & (outstanding < cfg["mshr_limit"]) & ~is_mc
            buf, sent = inject(
                buf, want, request_sub(tile_cls),
                dict(dest=dest, src=rid, cls=tile_cls, stamp=jnp.full(R, t)),
                masks)
            backlog = backlog - sent
            tile = dict(backlog=backlog, outstanding=outstanding + sent)
            n = lambda m: m.sum(dtype=i32)
            add = dict(
                gpu_push=n(sent & is_gpu), cpu_push=n(sent & is_cpu),
                gpu_stall_icnt=n(is_gpu & (backlog > 0)),
                gpu_stall_dram=ev["dram_gpu"],
                gpu_done=n(back & (ev["cls"] == 1)),
                cpu_done=n(back & (ev["cls"] == 0)),
                gpu_gen=n(gen & is_gpu), cpu_gen=n(gen & is_cpu),
                lat_sum=lat.sum(dtype=i32), lat_cnt=n(ev["eject"]),
                cpu_lat_sum=jnp.where(cpu_ej, lat, 0).sum(dtype=i32),
                cpu_lat_cnt=n(cpu_ej),
                gpu_lat_sum=jnp.where(gpu_ej, lat, 0).sum(dtype=i32),
                gpu_lat_cnt=n(gpu_ej), moved=ev["moved"])
            c = {k: c[k] + add[k] for k in COUNTERS}
            return (buf, mcq, tile, phase, c), None

        t0 = e * L
        c0 = {k: i32(0) for k in COUNTERS}
        (buf, mcq, tile, phase, c), _ = jax.lax.scan(
            cycle, (buf, mcq, tile, phase, c0),
            (t0 + jnp.arange(L, dtype=i32), jax.random.split(key, L)))
        t_end = t0 + L

        # observations, then the telemetry fault of the epoch
        scale = jnp.asarray(cfg["z_scales"], f32) * 0.5
        raw = jnp.stack([c["gpu_stall_dram"], c["gpu_push"],
                         c["gpu_stall_icnt"]]).astype(f32)
        z = rnd(jnp.clip((raw - scale) / scale, -1.0, 1.0))
        z = jnp.where(tmode == TELEM_DROP, f32(-1.0), z)
        z = jnp.where(tmode == TELEM_SPIKE, rnd(z + tmag), z)
        z = jnp.where(tmode == TELEM_NAN, f32(jnp.nan), z)

        # the KF (paper Eqs. 1-5) in closed form, as in noc.py
        r = f32(cfg["kf_r"])
        p_prior = rnd(kf["p"] + f32(cfg["kf_q"]))
        gain = rnd(p_prior / (3 * p_prior + r))
        nu = rnd(z - kf["x"])
        x_post = rnd(kf["x"] + gain * rnd(jnp.sum(nu)))
        p_post = rnd((1 - 3 * gain) * p_prior)
        # the guard: NIS with S^-1 = (I - P^ 1 1^T / (r + 3 P^)) / r
        nis = rnd((jnp.sum(nu * nu)
                   - p_prior * jnp.sum(nu) ** 2 / (r + 3 * p_prior)) / r)
        reject = guard & (~jnp.isfinite(z).all() | (nis > cfg["nis_threshold"]))
        x = jnp.where(reject, kf["x"], x_post)
        p = jnp.where(reject, p_prior, p_post)
        run = jnp.where(reject, kf["run"] + 1, 0)
        cov_bad = ~jnp.isfinite(p) | (p > cfg["cov_limit"])
        reset = guard & ((run == cfg["watchdog_limit"]) | cov_bad)
        x = jnp.where(reset & ~jnp.isfinite(x), f32(0.0), x)
        p = jnp.where(reset, f32(1.0), p)
        healthy = ~guard | ~((run >= cfg["watchdog_limit"]) | cov_bad)
        kf = dict(x=x, p=p, run=run)
        signal = (x > 0).astype(i32)

        # hysteresis (paper §3.2), only where the KF drives the network
        over = (boosted & (pol["since"] >= 0)
                & (t_end - pol["since"] > cfg["revert"]))
        held = (t_end < cfg["warmup"]) | ((t_end - pol["last"] < cfg["hold"])
                                          & ~over)
        new = jnp.where(held, pol["config"], jnp.where(over, 0, signal))
        if not kf_drives:
            new = pol["config"]
        since = jnp.where(new > 0, jnp.where(boosted, pol["since"], t_end), -1)
        # an unhealthy filter falls back to the fair split
        pol = dict(config=jnp.where(healthy, new, 0),
                   last=jnp.where(new != pol["config"], t_end, pol["last"]),
                   since=jnp.where(healthy, since, -1))

        fl = lambda k: c[k].astype(f32)
        cpu_lat = fl("cpu_lat_sum") / jnp.maximum(fl("cpu_lat_cnt"), 1.0)
        out = dict(
            gpu_ipc=jnp.where(c["gpu_gen"] > 0, jnp.minimum(
                fl("gpu_done") / jnp.maximum(fl("gpu_gen"), 1.0), 1.0),
                1.0) * GPU_BASE_IPC,
            cpu_ipc=1.0 / (1.0 + CPU_LAT_SENSITIVITY
                           * jnp.maximum(cpu_lat - CPU_NOLOAD_LAT, 0.0)),
            avg_latency=fl("lat_sum") / jnp.maximum(fl("lat_cnt"), 1.0),
            gpu_inj_rate=fl("gpu_push") / (L * is_gpu.sum(dtype=i32)).astype(
                f32))
        out = {k: rnd(v.astype(f32)) for k, v in out.items()}
        out.update(c, kf_signal=signal, applied_config=pol["config"],
                   gpu_vc_quota=masks[1].sum(dtype=i32),
                   # what the deployment did this epoch (not compared)
                   relocated=(tile_kind != base).any(),
                   kf_rejected=reject, links_down=(~links).sum(dtype=i32))
        return (buf, mcq, tile, phase, kf, pol), out

    zeros = lambda *shape: jnp.zeros(shape, i32)
    buf = {f: zeros(n_sub, R, PORTS, V, B) for f in FIELDS}
    buf.update(head=zeros(n_sub, R, PORTS, V), count=zeros(n_sub, R, PORTS, V),
               rr=zeros(n_sub, R, PORTS))
    mcq = dict(src=zeros(R, Q), cls=zeros(R, Q), head=zeros(R),
               count=zeros(R), timer=zeros(R), staged=jnp.zeros(R, bool),
               st_dst=zeros(R), st_cls=zeros(R))
    tile = dict(backlog=zeros(R), outstanding=zeros(R))
    kf = dict(x=f32(0), p=f32(1), run=i32(0))
    pol = dict(config=i32(0), last=i32(-10**9), since=i32(-1))
    keys = jax.random.split(jax.random.PRNGKey(seed), E)
    carry = (buf, mcq, tile, i32(0), kf, pol)
    _, out = jax.lax.scan(epoch, carry, (
        jnp.arange(E, dtype=i32), keys, demand, link_ok, router_ok, telem,
        mag, cls0, cls1))
    return out


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _batch(cfg, mode, lowp, *streams):
    return jax.vmap(lambda *xs: _simulate(dict(cfg), mode, lowp, *xs))(
        *streams)


def settings(config: dict) -> dict:
    """The model's settings from a configuration file: its `noc` block, its
    hysteresis (paper §3.2; `noc.py`'s constants where the file gives none)
    and its guard's thresholds."""
    cfg = dict(config["noc"])
    cfg.update(dict(warmup=WARMUP, hold=HOLD, revert=REVERT),
               **config.get("hysteresis", {}))
    cfg.update(config["guard_thresholds"])
    return cfg


def simulate(config: dict, points: list[dict], lowp=False):
    """One dict of (n_epochs,) numpy arrays per point, in order.  `config`
    is a configuration file (`noc`, `hysteresis`, `guard`,
    `guard_thresholds`, `placement`); a point is a dict of `mode`,
    `control`, `segments` (its demand), `faults` (its fault events) and
    `seed`.  The program of each mode compiles once; then each point runs
    on its own host thread."""
    cfg = settings(config)
    E = cfg["n_epochs"]
    _, neighbour, kind, mcs = package(cfg["width"], cfg["height"], cfg["n_mc"])
    base, boost = placement_rows(config["placement"], E, cfg["width"], kind,
                                 mcs)
    frozen = tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                          for k, v in cfg.items()))
    cpu = jax.devices("cpu")[0]

    def streams(point):
        bw, place = CONTROLS[point["control"]]
        return tuple(np.asarray(s)[None] for s in (
            demand_rows(point["segments"], E),
            *fault_rows(point["faults"], E, neighbour), base, boost,
            np.asarray([bw, place, bool(config["guard"])]),
            np.int32(point["seed"])))

    args = [streams(p) for p in points]
    with jax.default_device(cpu):
        programs = {p["mode"]: _batch.lower(frozen, p["mode"], lowp,
                                            *a).compile()
                    for p, a in zip(points, args)}

    def one(i):
        with jax.default_device(cpu):
            out = programs[points[i]["mode"]](*args[i])
            return {k: v[0] for k, v in jax.device_get(out).items()}

    with ThreadPoolExecutor(max(1, min(len(points), os.cpu_count() or 1))) as ex:
        return list(ex.map(one, range(len(points))))
