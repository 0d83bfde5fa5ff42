"""Training traffic: steps of one compiled step on seeded batches cycled
from host memory, a bounded number in flight.

Parameters (the mix's file): batch, seq, batches (distinct batches cycled),
in_flight (the loss of step i - in_flight is fetched before step i is
enqueued), trace_seconds (the profiled stretch at the window's end).
"""
import collections
import gc
import time

import numpy as np

from .. import compare, harness, train_check, weights as W
from ..arch import build_program_model, load as load_arch


class TrainSystem:
    """The system under test: the program's model, optimizer and compiled
    step, built once and handed from set-up to the window."""

    def __init__(self, run):
        import paddle_tpu as paddle
        from paddle_tpu.jit import TrainStep
        run.lap("import")
        cfg, t = run.config, run.traffic
        self.paddle = paddle
        self.arch = load_arch(cfg["arch"])
        self.d = self.arch.dims(cfg)
        self.layout = self.arch.layout(self.d)
        self.hyper = cfg["training"]["optimizer"]
        self.model = build_program_model(cfg)
        run.lap("model_built")
        h = self.hyper
        self.opt = paddle.optimizer.AdamW(
            learning_rate=h["lr"], beta1=h["beta1"], beta2=h["beta2"],
            epsilon=h["eps"], weight_decay=h["weight_decay"],
            parameters=self.model.parameters())
        amp = cfg["training"]["amp"]
        if "mesh" in t:
            # the data-parallel step over the cell's chips
            from paddle_tpu import parallel
            mesh = parallel.create_mesh(t["mesh"], devices=run.devices)
            strategy = parallel.DistributedStrategy(amp=True)
            strategy.amp_configs.dtype = amp["dtype"]
            self.step = parallel.ShardedTrainStep(
                self.model, self.arch.train_loss_fn(), self.opt,
                strategy=strategy, mesh=mesh)
        else:
            self.step = TrainStep(self.model, self.arch.train_loss_fn(),
                                  self.opt, amp_level=amp["level"],
                                  amp_dtype=amp["dtype"])
        self.tokens_per_step = t["batch"] * t["seq"]
        self._shape = (t["batch"], t["seq"], t["batches"],
                       cfg["training"]["vocab_used"])
        self.reseed(run.seed)
        run.lap("weights_and_batches")

    def reseed(self, seed):
        """Weights, optimizer state and batches as a new run of `seed`
        starts with them; the compiled step stays."""
        train_check.load_weights(self.arch, self.d, self.model,
                                 W.make(self.layout, seed))
        self.step._opt_state = None
        self.opt._step_count = 0
        self.batches = self.arch.train_batches(self.d, seed, *self._shape)

    def feed(self, i):
        """The window's feed: batch i of the cycle, from host memory."""
        prog, _ = self.batches[i % len(self.batches)]
        return tuple(self.paddle.to_tensor(x) for x in prog)

    def call(self, i):
        """The window's call: enqueues step i, returns its loss unfetched."""
        return self.step(*self.feed(i))

    def warmup(self):
        return self.step.warmup(*self.feed(0))

    def executables(self):
        return len(self.step._compiled._exe)

    def first_steps(self, seed):
        """Steps 1-3 through `call`, with the readings `correct` needs."""
        losses = [float(self.call(0))]
        grad_norms = train_check.program_grad_norms(
            self.arch, self.d, self.step, self.hyper["beta1"])
        for i in (1, 2):
            losses.append(float(self.call(i)))
        change = train_check.program_change_norms(
            self.arch, self.d, self.model, self.layout, seed)
        return {"losses": losses, "grad_norms": grad_norms,
                "change_norms": change}

    def free(self):
        self.step._opt_state = None
        self.step = self.model = self.opt = None
        gc.collect()


def attention_paths():
    from paddle_tpu.observability.metrics import get_registry
    m = get_registry().get("attention_path_total")
    return {k[0]: v for k, v in m.samples()} if m is not None else {}


def window(run, system, start_index):
    """Steps for `run.seconds`, ended by the last loss being fetched.  A
    traced run profiles the last `trace_seconds` of the window; the trace
    is written out only after the window has closed."""
    t = run.traffic
    in_flight = t["in_flight"]
    pending = collections.deque()
    trace_from = run.seconds - t["trace_seconds"] if run.trace else None
    n, last = 0, float("nan")

    def fetch_all():
        nonlocal last
        while pending:
            last = float(pending.popleft())

    t0 = time.perf_counter()
    while True:
        while len(pending) >= in_flight:
            last = float(pending.popleft())
        now = time.perf_counter()
        if now - t0 >= run.seconds:
            break
        if trace_from is not None and now - t0 >= trace_from:
            fetch_all()                # the stretch starts on an idle device
            run.start_profile()
            run.traced["step0"] = n
            trace_from = None
        with run.span("train_step_call"):
            pending.append(system.call(start_index + n))
        n += 1
    fetch_all()
    t1 = run.end_mark() if run.traced is not None else time.perf_counter()
    if run.traced is not None:
        run.traced["steps"] = n - run.traced["step0"]
        run.stop_profile()
    run.window = (t0, t1)
    return n, last


def run(run):
    paths0 = attention_paths()
    system = TrainSystem(run)
    warm = system.warmup()
    run.lap("step_compiled_or_loaded")
    paths = {k: v - paths0.get(k, 0) for k, v in attention_paths().items()}
    prog = system.first_steps(run.seed)
    run.lap("first_three_steps_and_readings")
    run.setup_s = time.perf_counter() - run.t_start
    steps, last_loss = window(run, system, train_check.STEPS)
    t0, t1 = run.window
    exes = system.executables()
    from paddle_tpu import programs
    run.counters.update(
        attention_paths=paths, executables=exes,
        store=programs.store_stats(), compile_seconds=warm["seconds"])
    run.extra["memory_peak_bytes"] = harness.memory_peak_bytes(run.devices)
    run.extra["steps"] = steps
    run.extra["longest_step_call_ms"] = 1e3 * max(
        (e - s for name, s, e in run.spans if name == "train_step_call"),
        default=0.0)
    run.extra["tokens_per_step"] = system.tokens_per_step
    arch, d, layout, hyper = (system.arch, system.d, system.layout,
                              system.hyper)
    feeds = [system.batches[i][1] for i in range(train_check.STEPS)]
    system.free()
    t_ref = time.perf_counter()
    ref = train_check.reference_steps(arch, d, layout, run.seed, feeds, hyper)
    run.extra["reference_s"] = time.perf_counter() - t_ref
    numbers, where = compare.train_numbers(prog, ref)
    numbers["compiles_in_window"] = float(exes - 1)
    if not np.isfinite(last_loss):
        numbers["loss_gap"] = float("inf")
    run.extra["worst_leaves"] = where
    run.extra["losses"] = {"program": prog["losses"],
                           "reference": ref["losses"]}
    return {
        "attempted": steps, "failed": 0,
        "end_to_end": {
            "train_tokens_per_s": steps * system.tokens_per_step / (t1 - t0),
        },
        "numbers": numbers,
    }
