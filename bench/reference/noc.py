"""The plain reference of the NoC sweep, written from the model's semantics.

`simulate` runs sweep points (a package, a network mode, a workload, a seed)
for `n_epochs` epochs of `epoch_len` cycles and returns, by name, the
per-epoch readings the simulator reports.  It imports nothing of the program
under test and is written for clarity, not speed: each FIFO keeps its packet
fields in plain int32 arrays, each network mode has its own number of
subnets and VCs, every scatter is an indexed write, and each cycle draws its
randomness from its own key.  It runs on the host CPU.

The model (arXiv:2406.00568 §3-4, Table 1, Figs. 6-8, as the simulator
documents it):

- Package: a WxH mesh, router r = y*W + x, ports N E S W and Local.  MCs sit
  on the top and bottom rows, spread evenly; the other tiles alternate CPU,
  GPU in router order.  XY routing.  Single-flit packets.
- Networks: `baseline` (2 subnets, request and reply, every VC shared),
  `fair` (VCs split half and half), `kf` (the split of `fair`, or 3:1 for the
  GPU with switch priority GPU, GPU, CPU by cycle when the KF boosts), and
  `4subnet` (a request and a reply subnet per class, half the VCs each, even
  subnets switching on even cycles and odd ones on odd cycles).
- A cycle, in order:
  1. the reply an MC staged last cycle tries its reply subnet's Local input
     (first VC its class may use with room);
  2. an MC takes request ejections this cycle only if its queue, before
     service, has room for one from every request subnet;
  3. an MC with requests and no staged reply counts its service timer down
     and, at 0, stages the reply to its oldest request and rearms the timer;
  4. every router: each output picks, among head packets that want it, the
     next after its round-robin pointer (packets of the class the switch
     favours this cycle first); it fires if its subnet switches this cycle
     and the sink takes the packet (Local) or the next router's input has a
     VC the packet's class may use with room (at the start of the cycle); an
     input port that wins several outputs keeps the lowest; a fired output
     moves its pointer past the winner and the packet to the tail of that VC;
     a Local output whose sink refuses counts a DRAM stall of its winner's
     class;
  5. requests ejected at an MC join its queue, in subnet order;
  6. replies ejected at a tile complete one of its outstanding requests;
  7. every ejected packet adds its age (cycle - injection cycle) to latency;
  8. the workload's burst phase steps, each tile draws a request against its
     class's rate, and a full source queue drops it;
  9. a tile with queued requests and fewer than `mshr_limit` outstanding sends
     one into its request subnet, to a uniformly drawn MC.
- An epoch ends with the KF step on the normalised counters (GPU DRAM stalls,
  GPU pushes, GPU injection stalls), the hysteresis of §3.2 (warm-up 10,000
  cycles, hold 5,000, revert after 10,000 boosted), which sets the next
  epoch's VC split, and the epoch's IPC and latency readings.

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

NORTH, EAST, SOUTH, WEST, LOCAL = range(5)
PORTS = 5
# the input port a packet leaving through each output enters next door
ACROSS = np.array([SOUTH, WEST, NORTH, EAST, LOCAL])
CPU, GPU, MC = 0, 1, 2
SOURCE_QUEUE = 64          # requests a tile holds before it drops new ones
WARMUP, HOLD, REVERT = 10_000, 5_000, 10_000   # paper §3.2, in cycles
GPU_BASE_IPC = 1.0
CPU_NOLOAD_LAT, CPU_LAT_SENSITIVITY = 14.0, 0.01

COUNTERS = ("gpu_push", "gpu_stall_icnt", "gpu_stall_dram", "cpu_push",
            "gpu_done", "cpu_done", "gpu_gen", "cpu_gen", "lat_sum",
            "lat_cnt", "cpu_lat_sum", "cpu_lat_cnt", "gpu_lat_sum",
            "gpu_lat_cnt", "moved")
FLOATS = ("gpu_ipc", "cpu_ipc", "avg_latency", "gpu_inj_rate")
INTS = ("kf_signal", "applied_config", "gpu_vc_quota") + COUNTERS
FIELDS = ("dest", "src", "cls", "stamp")
WORKLOAD = ("gpu_rate_lo", "gpu_rate_hi", "p_enter", "p_exit", "cpu_rate")


def package(width: int, height: int, n_mc: int):
    """(route, neighbour, kind, mcs) tables of a mesh."""
    n = width * height
    route = np.full((n, n), LOCAL, np.int32)
    neighbour = np.full((n, PORTS), -1, np.int32)
    for r in range(n):
        x, y = r % width, r // width
        if y > 0:
            neighbour[r, NORTH] = r - width
        if x < width - 1:
            neighbour[r, EAST] = r + 1
        if y < height - 1:
            neighbour[r, SOUTH] = r + width
        if x > 0:
            neighbour[r, WEST] = r - 1
        for d in range(n):
            dx, dy = d % width, d // width
            route[r, d] = (EAST if dx > x else WEST if dx < x else
                           SOUTH if dy > y else NORTH if dy < y else LOCAL)

    def columns(k):
        return {int(c) for c in np.linspace(0, width - 1, k).round()}

    mcs = sorted(columns(n_mc // 2)
                 | {(height - 1) * width + c for c in columns(n_mc - n_mc // 2)})
    assert len(mcs) == n_mc, (width, height, n_mc)
    kind = np.full(n, MC, np.int32)
    tiles = [r for r in range(n) if r not in mcs]
    kind[tiles] = np.arange(len(tiles)) % 2       # CPU, GPU, CPU, ...
    return route, neighbour, kind, np.asarray(mcs, np.int32)


def network(mode: str, n_vcs: int):
    """(subnets, VCs per subnet, {config: (GPU VCs, CPU VCs)}, KF drives)."""
    v = np.arange(n_vcs)
    half = (v < n_vcs // 2, v >= n_vcs // 2)
    if mode == "baseline":
        split = {0: (v >= 0, v >= 0)}
    elif mode == "fair":
        split = {0: half}
    elif mode == "kf":
        split = {0: half, 1: (v < n_vcs - 1, v == n_vcs - 1)}
    elif mode == "4subnet":
        w = np.arange(n_vcs // 2) >= 0
        return 4, n_vcs // 2, {0: (w, w), 1: (w, w)}, False
    else:
        raise ValueError(f"unknown mode {mode!r}")
    split.setdefault(1, split[0])
    return 2, n_vcs, split, mode == "kf"


def _simulate(cfg: dict, mode: str, lowp: bool, workload, seed):
    route, neighbour, kind, mcs = package(cfg["width"], cfg["height"],
                                          cfg["n_mc"])
    n_sub, V, split, kf_drives = network(mode, cfg["n_vcs"])
    R, B, Q = len(kind), cfg["buf_depth"], cfg["mc_queue_cap"]
    E, L = cfg["n_epochs"], cfg["epoch_len"]
    i32, f32 = jnp.int32, jnp.float32
    rnd = ((lambda x: jax.lax.reduce_precision(x, 8, 7)) if lowp
           else (lambda x: x))

    route, neighbour = jnp.asarray(route), jnp.asarray(neighbour)
    mcs, rid = jnp.asarray(mcs), jnp.arange(R)
    is_mc, is_gpu, is_cpu = kind == MC, kind == GPU, kind == CPU
    tile_cls = jnp.asarray(is_gpu, i32)
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
    lo, hi, p_enter, p_exit, cpu_rate = (workload[i] for i in range(5))
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

    def switch(buf, accept, active, favour, masks):
        """Step 4 on every router of every subnet; returns the new FIFOs and
        what left through the Local outputs."""
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
        fire = wanted & active[:, None, None] & jnp.where(
            sink, accept[..., None], (neighbour >= 0) & room)
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
        e, key = x
        boosted = pol["config"] > 0
        masks = jnp.stack([cpu_vcs[pol["config"]], gpu_vcs[pol["config"]]])
        favour_on = kf_drives & boosted

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
            buf, ev = switch(buf, accept, active, favour, masks)
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
            # 8. generation
            k_phase, k_gen, k_dest = jax.random.split(key, 3)
            u = jax.random.uniform(k_phase, ())
            phase = jnp.where(phase == 0, jnp.where(u < p_enter, 1, 0),
                              jnp.where(u < p_exit, 0, 1))
            rate = jnp.where(is_gpu, jnp.where(phase == 1, hi, lo),
                             jnp.where(is_cpu, cpu_rate, 0.0))
            gen = (jax.random.uniform(k_gen, (R,), f32) < rate) & ~is_mc
            backlog = tile["backlog"] + (gen & (tile["backlog"] < SOURCE_QUEUE))
            # 9. one request per tile
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

        # the KF (paper Eqs. 1-5): scalar state, A = 1, H = [1 1 1]^T,
        # R = r I, so every observation gets the gain P^ / (3 P^ + r)
        scale = jnp.asarray(cfg["z_scales"], f32) * 0.5
        raw = jnp.stack([c["gpu_stall_dram"], c["gpu_push"],
                         c["gpu_stall_icnt"]]).astype(f32)
        z = rnd(jnp.clip((raw - scale) / scale, -1.0, 1.0))
        p_prior = rnd(kf["p"] + f32(cfg["kf_q"]))
        gain = rnd(p_prior / (3 * p_prior + f32(cfg["kf_r"])))
        x = rnd(kf["x"] + gain * rnd(jnp.sum(z - kf["x"])))
        kf = dict(x=x, p=rnd((1 - 3 * gain) * p_prior))
        signal = (x > 0).astype(i32)

        # hysteresis (paper §3.2), only where the KF drives the network
        over = boosted & (pol["since"] >= 0) & (t_end - pol["since"] > REVERT)
        held = (t_end < WARMUP) | ((t_end - pol["last"] < HOLD) & ~over)
        new = jnp.where(held, pol["config"], jnp.where(over, 0, signal))
        if not kf_drives:
            new = pol["config"]
        pol = dict(config=new,
                   last=jnp.where(new != pol["config"], t_end, pol["last"]),
                   since=jnp.where(new > 0, jnp.where(boosted, pol["since"],
                                                      t_end), -1))

        fl = lambda k: c[k].astype(f32)
        cpu_lat = fl("cpu_lat_sum") / jnp.maximum(fl("cpu_lat_cnt"), 1.0)
        out = dict(
            gpu_ipc=jnp.where(c["gpu_gen"] > 0, jnp.minimum(
                fl("gpu_done") / jnp.maximum(fl("gpu_gen"), 1.0), 1.0),
                1.0) * GPU_BASE_IPC,
            cpu_ipc=1.0 / (1.0 + CPU_LAT_SENSITIVITY
                           * jnp.maximum(cpu_lat - CPU_NOLOAD_LAT, 0.0)),
            avg_latency=fl("lat_sum") / jnp.maximum(fl("lat_cnt"), 1.0),
            gpu_inj_rate=fl("gpu_push") / f32(L * int(is_gpu.sum())))
        out = {k: rnd(v.astype(f32)) for k, v in out.items()}
        out.update(c, kf_signal=signal, applied_config=new,
                   gpu_vc_quota=masks[1].sum(dtype=i32))
        return (buf, mcq, tile, phase, kf, pol), out

    zeros = lambda *shape: jnp.zeros(shape, i32)
    buf = {f: zeros(n_sub, R, PORTS, V, B) for f in FIELDS}
    buf.update(head=zeros(n_sub, R, PORTS, V), count=zeros(n_sub, R, PORTS, V),
               rr=zeros(n_sub, R, PORTS))
    mcq = dict(src=zeros(R, Q), cls=zeros(R, Q), head=zeros(R),
               count=zeros(R), timer=zeros(R), staged=jnp.zeros(R, bool),
               st_dst=zeros(R), st_cls=zeros(R))
    tile = dict(backlog=zeros(R), outstanding=zeros(R))
    kf = dict(x=f32(0), p=f32(1))
    pol = dict(config=i32(0), last=i32(-10**9), since=i32(-1))
    keys = jax.random.split(jax.random.PRNGKey(seed), E)
    carry = (buf, mcq, tile, i32(0), kf, pol)
    _, out = jax.lax.scan(epoch, carry, (jnp.arange(E, dtype=i32), keys))
    return out


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _batch(cfg, mode, lowp, workloads, seeds):
    return jax.vmap(lambda w, s: _simulate(dict(cfg), mode, lowp, w, s))(
        workloads, seeds)


def simulate(cfg: dict, points: list[tuple[str, dict, int]], lowp=False):
    """One dict of (n_epochs,) numpy arrays per (mode, workload, seed) point,
    in order.  `cfg` holds the configuration file's `noc` fields and a
    workload its five rates.  Each point runs on its own host thread."""
    frozen = tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                          for k, v in cfg.items()))
    cpu = jax.devices("cpu")[0]

    def one(point):
        mode, workload, seed = point
        with jax.default_device(cpu):
            w = jnp.asarray([[workload[k] for k in WORKLOAD]], jnp.float32)
            out = _batch(frozen, mode, lowp, w, jnp.asarray([seed], jnp.int32))
            return {k: v[0] for k, v in jax.device_get(out).items()}

    # one point of each mode first, so that each program compiles once
    first = {m: i for i, (m, _, _) in reversed(list(enumerate(points)))}
    rest = [i for i in range(len(points)) if i not in first.values()]
    rows = [None] * len(points)
    with ThreadPoolExecutor(max(1, min(len(points), os.cpu_count() or 1))) as ex:
        for idx in (list(first.values()), rest):
            for i, row in zip(idx, ex.map(one, [points[i] for i in idx])):
                rows[i] = row
    return rows
