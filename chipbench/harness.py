"""One run of one cell: set-up, measured window, trace reduction, check.

Everything a cell needs is found by name: the workload's entry in
``BENCHMARK.json`` names a configuration (its ``file``) and a traffic
mix (``chipbench/traffic/<traffic>.json``); the configuration names its
family (``chipbench/families/<family>.py``, the plain reference); the
cell's limits are ``chipbench/limits/<workload>.json``; each per-layer
metric is read by ``chipbench/metrics/<metric>.py``.
"""

from __future__ import annotations

import dataclasses
import functools
import gc
import importlib
import importlib.util
import json
import os
import shutil
import statistics
import time
from typing import Callable, Dict, List, Optional

import numpy as np

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
TRACE_DIR = os.path.join(BENCH_DIR, ".trace")
#: a traced run profiles a window of at most this many seconds
TRACE_SECONDS = 5.0


# ---------------------------------------------------------------------------
# Cells
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: Optional[dict]
    end_to_end: List[str]
    per_layer: List[str]
    units: Dict[str, str]
    root: str = ROOT

    @property
    def clients(self) -> int:
        return self.traffic["clients_per_chip"] * self.chips

    @property
    def tokens_per_round(self) -> int:
        t = self.traffic
        return self.clients * t["batch"] * t["seq_len"]


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _reports(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(name: str, root: str = ROOT) -> Cell:
    """The cell ``name`` of ``root``'s ``BENCHMARK.json``."""
    bench = _read_json(os.path.join(root, "BENCHMARK.json"))
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r}; have {sorted(work)}")
    w = work[name]
    cfg = {c["name"]: c for c in bench["configs"]}[w["config"]]
    e2e = [m["name"] for m in bench["end_to_end"] if _reports(m, name)]
    layer = [m["name"] for m in bench["per_layer"]
             if _reports(m, name) and m["moves"] in e2e]
    limits = os.path.join(root, "chipbench", "limits", f"{name}.json")
    return Cell(
        name=name, chips=int(w["chips"]),
        config=_read_json(os.path.join(root, cfg["file"])),
        traffic=_read_json(os.path.join(root, "chipbench", "traffic",
                                        f"{w['traffic']}.json")),
        limits=_read_json(limits) if os.path.exists(limits) else None,
        end_to_end=e2e, per_layer=layer,
        units={m["name"]: m["unit"]
               for m in bench["end_to_end"] + bench["per_layer"]},
        root=root)


@functools.lru_cache(maxsize=None)
def _load(root: str, kind: str, name: str):
    """``<root>/chipbench/<kind>/<name>.py`` as a module of the package."""
    path = os.path.join(root, "chipbench", kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"chipbench.{kind}.{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def family(config: dict, root: str = ROOT):
    """The plain reference of a configuration's family."""
    return _load(root, "families", config["family"])


def metric_reader(name: str, root: str = ROOT) -> Callable:
    return _load(root, "metrics", name).read


def peaks(device_kind: str) -> dict:
    table = _read_json(os.path.join(BENCH_DIR, "peaks.json"))["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"have {sorted(table)}")
    return table[device_kind]


def arch_config(config: dict):
    """The program's ``ArchConfig`` for a configuration file."""
    from repro.models.config import ArchConfig, SSMConfig
    m = dict(config["model"])
    if "ssm" in m:
        m["ssm"] = SSMConfig(**m["ssm"])
    return ArchConfig(name=config["name"], family=config["family"], **m)


def client_key(seed: int, node: int):
    import jax
    key = jax.random.fold_in(jax.random.PRNGKey(seed % 2 ** 31),
                             seed // 2 ** 31)
    return jax.random.fold_in(key, node)


# ---------------------------------------------------------------------------
# Clocks
# ---------------------------------------------------------------------------

class CompileClock:
    """Counts JAX's tracing/compile/cache-load events and sums their
    seconds while registered."""

    def __init__(self):
        import jax
        self.events = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def close(self) -> None:
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if "compil" in event or "trace" in event:
            self.events += 1
            self.seconds += duration


class _GcClock:
    """Seconds the interpreter's garbage collector ran while registered."""

    def __init__(self):
        self.seconds, self.collections, self._t = 0.0, 0, None
        gc.callbacks.append(self._on)

    def close(self) -> None:
        gc.callbacks.remove(self._on)

    def _on(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.seconds += time.perf_counter() - self._t
            self.collections += 1


def annotation(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


# ---------------------------------------------------------------------------
# The system under test
# ---------------------------------------------------------------------------

def _simulator(n: int, seed: int):
    from repro.core.ndmp import Simulator
    sim = Simulator(num_spaces=3, latency=0.05, heartbeat_period=0.5,
                    probe_period=1.0, seed=seed)
    sim.seed_network(list(range(n)))
    return sim


def build_trainer(cell: Cell, seed: int, devices):
    """The gossip-training main path (after ``chip_smoke.build_trainer``):
    an NDMP-driven ``OverlayController`` over a ``SlotTrainLoop`` with the
    masked, donated ``dfl_train_bundle`` step.  Weights come from the
    configuration's family ``init`` and rows from the traffic mix."""
    import jax
    import jax.numpy as jnp
    from repro.configs import INPUT_SHAPES
    from repro.dist.compat import make_mesh
    from repro.launch.steps import dfl_train_bundle
    from repro.optim.optimizers import adamw
    from repro.overlay import OverlayController
    from repro.runtime import SlotTrainLoop, counting_jit

    from . import tokens

    t, o = cell.traffic, cell.config["optimizer"]
    clients = cell.clients
    if cell.chips == 1:
        mesh = make_mesh((1, 1), ("data", "model"), devices=devices[:1])
        loop_mesh, per_device = None, clients
    else:
        mesh = make_mesh((cell.chips, 1), ("data", "model"),
                         devices=devices[:cell.chips])
        loop_mesh, per_device = mesh, t["clients_per_chip"]
    cfg = arch_config(cell.config)
    shape = dataclasses.replace(INPUT_SHAPES["train_4k"],
                                global_batch=clients * t["batch"],
                                seq_len=t["seq_len"])
    opt = adamw(o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
                weight_decay=o["weight_decay"])
    bundle = dfl_train_bundle(cfg, shape, mesh, opt, dtype=jnp.bfloat16,
                              sync="none", masked=True,
                              clients_per_device=per_device)
    step, traces = counting_jit(bundle.step, donate_argnums=(0, 1))
    fam = family(cell.config, cell.root)
    init = jax.jit(functools.partial(fam.init, cell.config["model"],
                                     dtype=jnp.bfloat16))
    vocab = cell.config["model"]["vocab_size"]
    streams: Dict[int, object] = {}

    def make_params(node):
        with annotation("chipbench.make_params"):
            return init(client_key(seed, node))

    def make_batch(node_ids, _step):
        with annotation("chipbench.make_batch"):
            rows = []
            for u in node_ids:
                if u not in streams:
                    streams[u] = tokens.stream(t, vocab, seed, u)
                rows.append(next(streams[u]))
            toks, labels = zip(*rows)
            return {"tokens": jnp.asarray(np.stack(toks)),
                    "labels": jnp.asarray(np.stack(labels))}

    ctl = OverlayController(_simulator(clients, seed), capacity=clients,
                            fuse=t["fuse"], codec=t["codec"],
                            clients_per_device=per_device)
    return SlotTrainLoop(ctl, local_step=step, make_params=make_params,
                         optimizer=opt, make_batch=make_batch,
                         jit_local_step=False, trace_count=traces,
                         mesh=loop_mesh)


# ---------------------------------------------------------------------------
# Readings of the program's first steps
# ---------------------------------------------------------------------------

def _paths(tree) -> List[str]:
    import jax
    return ["/".join(str(getattr(k, "key", k)) for k in path)
            for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


@functools.lru_cache(maxsize=None)
def _delta_fn(root: str, family_name: str, model_json: str, index: int):
    """Per-row norm of (row - the clients' mean initial row) for leaf
    ``index``: the part of the change that mixing alone would not give.
    The initial leaves are made again from the clients' keys."""
    import jax
    import jax.numpy as jnp
    fam = _load(root, "families", family_name)
    model = json.loads(model_json)

    def leaf(key):
        tree = fam.init(model, key, dtype=jnp.bfloat16)
        return jax.tree.leaves(tree)[index].astype(jnp.float32)

    def norms(rows, keys):
        base = jnp.mean(jax.vmap(leaf)(keys), axis=0)
        diff = rows.astype(jnp.float32) - base
        return jnp.sqrt(jnp.sum(jnp.square(diff),
                                axis=tuple(range(1, rows.ndim))))
    return jax.jit(norms)


def delta_leaf_norms(cell: Cell, tree, nodes, keys
                     ) -> Dict[int, Dict[str, float]]:
    """{node: {leaf path: norm of (row - the clients' mean initial
    row)}} of a stacked tree whose rows are ``nodes``; ``keys`` are the
    keys of every client.  Leaf by leaf, so that one leaf's initial
    rows are alive at a time."""
    import jax
    model_json = json.dumps(cell.config["model"], sort_keys=True)
    out: Dict[int, Dict[str, float]] = {u: {} for u in nodes if u is not None}
    for i, (path, leaf) in enumerate(zip(_paths(tree),
                                         jax.tree.leaves(tree))):
        norms = jax.device_get(_delta_fn(
            cell.root, cell.config["family"], model_json, i)(leaf, keys))
        for r, u in enumerate(nodes):
            if u is not None:
                out[u][path] = float(norms[r])
    return out


def client_keys(seed: int, nodes) -> np.ndarray:
    """The initial keys of ``nodes``, in that order, on the host."""
    import jax
    return np.stack([np.asarray(jax.device_get(client_key(seed, u)))
                     for u in nodes])


@dataclasses.dataclass
class Readings:
    """What the comparison reads of three gossip rounds."""
    losses: List[float]                      # mean over clients, per round
    grad: Dict[int, Dict[str, np.ndarray]]   # first clipped gradient
    delta: Dict[int, Dict[str, float]]       # change after 3 rounds
    _grad_norms: Optional[Dict[int, Dict[str, float]]] = dataclasses.field(
        default=None, repr=False)

    def grad_norms(self) -> Dict[int, Dict[str, float]]:
        if self._grad_norms is None:
            self._grad_norms = {u: {p: _norm(g) for p, g in leaves.items()}
                                for u, leaves in self.grad.items()}
        return self._grad_norms


def _norm(x) -> float:
    """Euclidean norm in float32 blocks summed in float64: fast, and
    exact to about 1e-6 where one float32 sum would lose 1e-3."""
    x = np.ravel(np.asarray(x, np.float32))
    block = 1 << 18
    return float(np.sqrt(sum(float(np.dot(x[i:i + block], x[i:i + block]))
                             for i in range(0, x.size, block))))


def _kept(ref_grad: Dict[str, float]) -> List[str]:
    """Leaves whose reference gradient is at least a thousandth of the
    median leaf's; the others move by round-off alone."""
    gmed = statistics.median(ref_grad.values())
    return [p for p in ref_grad if ref_grad[p] >= 1e-3 * gmed]


def leaf_gap(got: Dict[int, Dict[str, float]],
             ref: Dict[int, Dict[str, float]],
             ref_grad: Dict[int, Dict[str, float]], stat=max) -> float:
    """``stat`` over the kept leaves and the clients of |norm -
    reference norm| over the larger of that leaf's and the median leaf's
    reference norm (the worst leaf by default)."""
    out = []
    for u, r in ref.items():
        keep = _kept(ref_grad[u])
        med = statistics.median(r[p] for p in keep)
        out += [abs(got[u][p] - r[p]) / max(r[p], med, 1e-30) for p in keep]
    return float(stat(out))


def grad_err(got: Readings, ref: Readings, stat=max) -> float:
    """``stat`` over the kept leaves and the clients of ||g - g_ref||
    over the larger of that leaf's and the median leaf's ||g_ref||: the
    first gradient's error itself, which a gap of norms averages away."""
    ref_norms = ref.grad_norms()
    errs = []
    for u, r in ref_norms.items():
        keep = _kept(r)
        med = statistics.median(r[p] for p in keep)
        for p in keep:
            d = np.subtract(got.grad[u][p], ref.grad[u][p], dtype=np.float32)
            errs.append(_norm(d) / max(r[p], med, 1e-30))
    return float(stat(errs))


def gaps(got: Readings, ref: Readings) -> Dict[str, float]:
    loss = max(abs(a - b) / abs(b) for a, b in zip(got.losses, ref.losses))
    ref_grad = ref.grad_norms()
    # the median leaf's change: on leaves whose AdamW step lies below a
    # bf16 step of their values, the change after three rounds is the
    # mixing's rounding alone, which a mixer that sums in bf16 (the
    # tree walk across chips) makes larger than the reference's
    return {"loss_gap": loss,
            "grad_gap": leaf_gap(got.grad_norms(), ref_grad, ref_grad),
            "grad_err": grad_err(got, ref),
            "update_gap": leaf_gap(got.delta, ref.delta, ref_grad,
                                   stat=statistics.median)}


def program_readings(loop, cell: Cell, seed: int, run_round) -> Readings:
    """Drive the loop through its first three rounds (the window's own
    call and feed) and read what the comparison needs."""
    import jax
    ctl = loop.controller
    nodes = [ctl.slots.node_at(s) for s in range(loop.capacity)]
    scale = np.float32(1.0 / (1.0 - cell.config["optimizer"]["b1"]))
    run_round()
    # after one step Adam's first moment is (1 - b1) * the clipped gradient
    mu = jax.device_get(loop.opt_state.mu)
    paths = _paths(mu)
    grad = {u: {p: np.asarray(l[i]) * scale
                for p, l in zip(paths, jax.tree.leaves(mu))}
            for i, u in enumerate(nodes) if u is not None}
    del mu
    run_round()
    run_round()
    keys = client_keys(seed, sorted(u for u in nodes if u is not None))
    delta = delta_leaf_norms(cell, loop.params, nodes, keys)
    losses = [r.loss for r in loop.records[:3]]
    return Readings(losses=losses, grad=grad, delta=delta)


# ---------------------------------------------------------------------------
# The plain reference
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _ref_fns(root: str, family_name: str, model_json: str, opt_json: str,
             quant: str, positions: Optional[int]):
    """(grad, step) of the plain reference: the loss and clipped float32
    gradient of one client's row, and one AdamW step on it."""
    import jax
    import jax.numpy as jnp
    from .families import common
    fam = _load(root, "families", family_name)
    model, o = json.loads(model_json), json.loads(opt_json)
    q = {"exact": common.exact, "fp8": common.fp8}[quant]

    def grad(params, tokens, labels):
        p32 = jax.tree.map(lambda l: l.astype(jnp.float32), params)
        loss, g = jax.value_and_grad(
            lambda p: fam.loss(model, p, tokens, labels, q=q,
                               positions=positions))(p32)
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(l))
                             for l in jax.tree.leaves(g)))
        return loss, jax.tree.map(
            lambda l: l * jnp.minimum(1.0, o["clip_global_norm"]
                                      / (gnorm + 1e-9)), g)

    def step(params, mu, nu, count, tokens, labels):
        loss, g = grad(params, tokens, labels)
        mu = jax.tree.map(lambda m, l: o["b1"] * m + (1 - o["b1"]) * l, mu, g)
        nu = jax.tree.map(lambda v, l: o["b2"] * v + (1 - o["b2"]) * l * l,
                          nu, g)
        c = count.astype(jnp.float32)
        params = jax.tree.map(
            lambda p, m, v: (p.astype(jnp.float32) - o["lr"] * (
                (m / (1 - o["b1"] ** c))
                / (jnp.sqrt(v / (1 - o["b2"] ** c)) + o["eps"])
                + o["weight_decay"] * p.astype(jnp.float32))).astype(p.dtype),
            params, mu, nu)
        return params, mu, nu, loss
    return jax.jit(grad), jax.jit(step, donate_argnums=(0, 1, 2))


@functools.lru_cache(maxsize=None)
def _mean_fn(n: int):
    import jax
    import jax.numpy as jnp
    return jax.jit(lambda *rows: (sum(r.astype(jnp.float32) for r in rows)
                                  / n).astype(rows[0].dtype))


def reference_readings(cell: Cell, seed: int, devices, *, quant="exact",
                       positions: Optional[int] = None, mix: bool = True,
                       lr_scale: float = 1.0) -> Readings:
    """Three gossip rounds of the plain reference, each client on the
    device that holds it in the program: AdamW on the float32 loss and
    gradient, parameters kept in the configuration's dtypes, then
    uniform averaging over the clients (the configuration's ``mixing``).
    ``quant``, ``positions``, ``mix`` and ``lr_scale`` make the control
    and the planted faults."""
    import jax
    import jax.numpy as jnp
    from . import tokens
    fam = family(cell.config, cell.root)
    model = cell.config["model"]
    o = dict(cell.config["optimizer"], lr=cell.config["optimizer"]["lr"]
             * lr_scale)
    model_json = json.dumps(model, sort_keys=True)
    grad_fn, step = _ref_fns(cell.root, cell.config["family"], model_json,
                             json.dumps(o, sort_keys=True), quant, positions)
    init = jax.jit(functools.partial(fam.init, model, dtype=jnp.bfloat16))
    nodes = list(range(cell.clients))
    per = cell.traffic["clients_per_chip"]
    dev = {u: devices[u // per] for u in nodes}
    batches = {}
    for u in nodes:
        stream = tokens.stream(cell.traffic, model["vocab_size"], seed, u)
        batches[u] = [next(stream) for _ in range(3)]
    params, mu, nu, grad = {}, {}, {}, {}
    for u in nodes:
        with jax.default_device(dev[u]):
            params[u] = init(client_key(seed, u))
            # the first gradient, read before any optimizer state exists
            _, g = grad_fn(params[u], *batches[u][0])
            paths = _paths(g)
            grad[u] = dict(zip(paths, jax.device_get(jax.tree.leaves(g))))
            del g
    for u in nodes:
        with jax.default_device(dev[u]):
            mu[u] = jax.tree.map(lambda l: jnp.zeros(l.shape, jnp.float32),
                                 params[u])
            nu[u] = jax.tree.map(jnp.zeros_like, mu[u])
    losses = []
    for k in range(3):
        round_loss = []
        for u in nodes:
            with jax.default_device(dev[u]):
                params[u], mu[u], nu[u], loss = step(
                    params[u], mu[u], nu[u], jnp.asarray(k + 1, jnp.int32),
                    *batches[u][k])
            round_loss.append(float(loss))
        losses.append(float(np.mean(round_loss)))
        if mix:
            mean = _mean_fn(len(nodes))
            leaves = {u: jax.tree.leaves(params[u]) for u in nodes}
            treedef = jax.tree.structure(params[0])
            for u in nodes:
                mixed = [mean(*[jax.device_put(leaves[v][i], dev[u])
                                for v in nodes])
                         for i in range(len(paths))]
                params[u] = jax.tree.unflatten(treedef, mixed)
            del leaves, mixed
    del mu, nu
    keys = client_keys(seed, nodes)
    delta = {}
    for u in nodes:
        with jax.default_device(dev[u]):
            stacked = jax.tree.map(lambda l: l[None], params[u])
            delta.update(delta_leaf_norms(cell, stacked, [u], keys))
        params[u] = None
    return Readings(losses=losses, grad=grad, delta=delta)


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------

def percentile(values, pct: float) -> float:
    """Linear-interpolated percentile (numpy's default method)."""
    return float(np.percentile(np.asarray(values, np.float64), pct))


def memory_peak(devices) -> int:
    peaks_ = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks_.append(int(stats["peak_bytes_in_use"]))
    return max(peaks_) if peaks_ else 0


def check(cell: Cell, got: Readings, ref: Readings):
    """(correct, {number: {"value", "limit"}}) against the cell's limits."""
    measured = gaps(got, ref)
    limits = (cell.limits or {}).get("limits", {})
    checks = {k: {"value": v, "limit": limits.get(k)}
              for k, v in measured.items()}
    correct = bool(limits) and all(
        c["limit"] is not None and np.isfinite(c["value"])
        and c["value"] <= c["limit"] for c in checks.values())
    return correct, checks


def run(cell: Cell, seed: int, seconds: float, trace: bool, devices,
        t_start: float, log=print, readings: Optional[dict] = None) -> dict:
    """Set-up, the measured window, the reading of the trace and the
    comparison with the reference.  Returns the result object; the
    program's and the reference's readings go into ``readings`` when it
    is given."""
    import jax

    devices = list(devices[:cell.chips])
    clock = CompileClock()
    try:
        loop = build_trainer(cell, seed, devices)

        def run_round():
            with annotation("chipbench.round"):
                return loop.run(1)

        got = program_readings(loop, cell, seed, run_round)
        # warm-up: rounds until one compiles nothing
        for _ in range(8):
            before = clock.events
            run_round()
            jax.block_until_ready(loop.params)
            if clock.events == before:
                break
        else:
            raise RuntimeError("warm-up rounds kept compiling")
        setup_s = time.perf_counter() - t_start
        log(f"setup_s={setup_s:.3f} compile_events={clock.events} "
            f"compile_s={clock.seconds:.3f}")

        if trace:
            seconds = min(seconds, TRACE_SECONDS)
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            jax.profiler.start_trace(TRACE_DIR)
        events_before = clock.events
        gc_s = _GcClock()
        round_s: List[float] = []
        first = len(loop.records)
        with annotation("chipbench.window"):
            start = time.perf_counter()
            while True:
                a = time.perf_counter()
                run_round()
                b = time.perf_counter()
                round_s.append(b - a)
                if b - start >= seconds:
                    break
            jax.block_until_ready(loop.params)
            end = time.perf_counter()
        gc_s.close()
        if trace:
            jax.profiler.stop_trace()
        window_s = end - start
        window_events = clock.events - events_before
        failed = sum(not np.isfinite(r.loss) for r in loop.records[first:])
    finally:
        clock.close()
    if window_events:
        raise RuntimeError(f"{window_events} compile events inside the "
                           f"measured window")
    peak = memory_peak(devices)
    rounds = len(round_s)
    tokens_per_s = rounds * cell.tokens_per_round / window_s
    kind = devices[0].device_kind
    result: dict = {
        "correct": False,
        "attempted": rounds,
        "failed": int(failed),
        "metrics": {},
        "device": {"platform": devices[0].platform, "kind": kind,
                   "count": len(devices), "memory_peak_bytes": peak},
    }
    e2e = {
        "tokens_per_s": tokens_per_s,
        "round_ms_p90": percentile(round_s, 90) * 1e3,
        "peak_hbm_gb": peak / 1e9,
        "setup_s": setup_s,
    }
    slowest = int(np.argmax(round_s))
    log(f"rounds={rounds} window_s={window_s:.4f} "
        f"round_ms_median={statistics.median(round_s) * 1e3:.4f} "
        f"slowest_round={slowest} ({round_s[slowest] * 1e3:.1f} ms) "
        f"gc_in_window_s={gc_s.seconds:.3f} ({gc_s.collections} "
        f"collections)")
    if trace:
        from . import trace as tr
        ctx = tr.Context.build(
            TRACE_DIR, cell=cell, rounds=rounds, window_s=window_s,
            tokens_per_s=tokens_per_s, peaks=peaks(kind),
            step_module=f"jit_{loop.local_step.__name__}",
            mix_module=f"jit_{loop.controller.mixer.__name__}",
            family=family(cell.config, cell.root), params=loop.params)
        for name in cell.per_layer:
            value = metric_reader(name, cell.root)(ctx)
            if value is not None:
                result["metrics"][name] = {"value": value,
                                           "unit": ctx.unit(name)}
        result["device"]["busy_s"] = ctx.busy_s
        result["device"]["window_s"] = ctx.window_s
        result["breakdown"] = ctx.breakdown()
        del ctx
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    else:
        for name in cell.end_to_end:
            result["metrics"][name] = {"value": e2e[name],
                                       "unit": cell.units[name]}

    # free the program's state before the reference runs
    loop.params = loop.opt_state = None
    del loop
    gc.collect()
    t_ref = time.perf_counter()
    ref = reference_readings(cell, seed, devices)
    if readings is not None:
        readings.update(program=got, reference=ref)
    correct, checks = check(cell, got, ref)
    log(f"reference_and_check_s={time.perf_counter() - t_ref:.3f}")
    result["correct"] = correct
    result["checks"] = checks
    return result
