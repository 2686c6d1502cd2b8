"""Training cells: the zoo trainer's own loop, ``launch/train.train_loop``,
with its prefetch, jitted step and per-step loss read-back.

The loop is driven as it is; the harness only swaps what the benchmark must
own, through the names ``train_loop`` looks up in its module:

* ``SyntheticLM``: the batches, made by ``bench.generate.lm_batch`` from the
  seed (every row different);
* ``init_train_state``: the initial weights, made on the device in one
  jitted call by the reference's ``init`` from the seed, laid out as the
  program's parameter tree and handed to the program's own optimizer state;
* ``Prefetcher``: the program's prefetcher, whose iterator also marks the
  window.  The first ``warm`` steps are set-up (the first compiles); the
  window opens when step ``warm`` is asked for and closes at the first step
  asked for after the deadline, so the step that crosses it counts, and
  so does every gap between steps.  With ``--trace 1`` a few more steps run
  under the profiler before the loop ends;
* ``build``: the program's own ``build``, whose step is wrapped to read the
  optimizer's state after the first step (the first gradient, from Adam's
  first moment) and after the third (the weights' change).

``correct``: after the window, with the program's state freed, the
reference runs the same three steps in float32 at the highest matmul
precision and the losses, the first gradient and the change are compared
leaf by leaf.
"""
from __future__ import annotations

import contextlib
import gc
import time

import numpy as np

from bench import compare, flops, generate, harness
from bench.reference import qwen2 as ref
from bench.spec import arch_spec

WARM = 3        # steps before the window: the first compiles; 3 are compared

# Limits, each between the largest reading of sound runs (bf16 program
# against the float32 reference, 21+ seeds) and the smallest reading of the
# control (float8 operands) or of a planted fault, with more room above the
# former (readings in PERF.md, one TPU v5e, zoo-qwen2-1.5b-8L).
LOSS_RTOL = 1.2e-4        # worst of the first three steps' relative loss gaps: 3.9e-5 .. 2.9e-4
GRAD_GAP = 2e-2           # worst leaf's first-gradient norm gap: 3.9e-3 .. 9.0e-2
CHANGE_GAP = 5e-2         # worst leaf's gap of the change after three steps: 1.4e-3 .. 1.0
# leaves whose reference gradient is under this share of the median leaf's
# move by round-off alone (a key bias under softmax): left out of the change
QUIET_LEAF = 1e-3


def to_program(p: dict) -> dict:
    """The reference's weights as the program's parameter tree."""
    lay = p["layers"]
    return {"embed": p["embed"], "final_norm": p["final_norm"],
            "stack": {"tail": {}, "blocks": {"sub0": {
                "norm1": lay["norm1"], "norm2": lay["norm2"],
                "mixer": {k: lay[k] for k in ("wq", "wk", "wv", "wo",
                                              "bq", "bk", "bv")},
                "ffn": {k: lay[k] for k in ("w_gate", "w_up", "w_down")}}}}}


def from_program(t: dict) -> dict:
    s = t["stack"]["blocks"]["sub0"]
    return {"embed": t["embed"], "final_norm": t["final_norm"],
            "layers": {"norm1": s["norm1"], "norm2": s["norm2"],
                       **s["mixer"], **s["ffn"]}}


def check_optimizer(opt: dict) -> None:
    """The program's AdamW constants must be those the configuration
    states: the loop takes only the learning rate and warm-up."""
    from repro.train.optimizer import OptConfig

    have = OptConfig()
    for k in ("b1", "b2", "eps", "weight_decay", "grad_clip", "decay_steps",
              "min_lr_ratio"):
        if getattr(have, k) != opt[k]:
            raise ValueError(f"the program's AdamW {k} is {getattr(have, k)}, "
                             f"the configuration states {opt[k]}")


class Window:
    def __init__(self, seconds: float, tail_steps: int, tools) -> None:
        self.seconds, self.tail_steps, self.tools = seconds, tail_steps, tools
        self.start = self.end = None
        self.steps = 0

    def prefetcher(self, base):
        window = self

        class WindowPrefetcher(base):
            def __iter__(self):
                tools = window.tools
                for step, batch in super().__iter__():
                    now = time.perf_counter()
                    if step == WARM:
                        window.start, deadline = now, now + window.seconds
                    elif window.end is None and step > WARM and now >= deadline:
                        window.end, window.steps = now, step - WARM
                        if not tools.trace:
                            return
                        tools.start_tail()
                        stop = step + window.tail_steps
                    if window.end is not None and step == stop:
                        tools.stop_tail(window.tail_steps)
                        return
                    with (tools.span("bench.train_step") if window.end
                          else contextlib.nullcontext()):
                        yield step, batch

        return WindowPrefetcher


class Capture:
    """Wraps the program's jitted step to read its optimizer state after the
    first and the third step, as leaf norms in the reference's naming."""

    def __init__(self, base_build, key, model_cfg: dict, b1: float) -> None:
        import jax

        self.base_build, self.key, self.b1 = base_build, key, b1
        self.calls = 0
        self.grad = self.change = None
        self._moment = jax.jit(lambda m: ref.leaf_norms(from_program(m)))
        self._change = jax.jit(lambda w, k: ref.leaf_norms(jax.tree.map(
            lambda a, b: a - b, from_program(w), ref.init(k, model_cfg))))

    def build(self, *a, **kw):
        jit_step, state, batch_sh = self.base_build(*a, **kw)

        def step(state, batch):
            state, metrics = jit_step(state, batch)
            self.calls += 1
            if self.calls == 1:
                self.grad = {k: v / (1 - self.b1) for k, v in
                             ref.to_host(self._moment(state["m"])).items()}
            elif self.calls == WARM:
                self.change = ref.to_host(self._change(
                    state.get("master", state["params"]), self.key))
            return state, metrics

        return step, state, batch_sh


@contextlib.contextmanager
def patched(module, **names):
    old = {k: getattr(module, k) for k in names}
    for k, v in names.items():
        setattr(module, k, v)
    try:
        yield
    finally:
        for k, v in old.items():
            setattr(module, k, v)


def run(cell: harness.Cell, tools: harness.Tools, devices) -> harness.Outcome:
    import jax
    from repro.launch import train as T
    from repro.models import model as M
    from repro.train import optimizer as O

    cfg, traffic = cell.config, cell.traffic
    training, opt = cfg["training"], cfg["training"]["optimizer"]
    check_optimizer(opt)
    spec = arch_spec(cfg)
    if (training["param_dtype"], training["compute_dtype"],
            training["master_dtype"]) != ("bfloat16", "bfloat16", "float32"):
        raise ValueError("the loop runs bf16 weights with an fp32 master copy")
    batch, seq = int(traffic["batch"]), int(traffic["seq"])
    s31 = harness.seed32(cell.seed)
    key = jax.random.PRNGKey(s31)
    args = T.parse_args([
        "--steps", str(10 ** 9), "--batch", str(batch), "--seq", str(seq),
        "--seed", str(s31), "--bf16", "--remat", training["remat"],
        "--mesh", cfg.get("mesh", ""), "--lr", repr(opt["lr"]),
        "--warmup", str(opt["warmup_steps"]), "--log-every", str(10 ** 9)])

    laid = jax.eval_shape(lambda k: to_program(ref.init(k, cfg)), key)
    if jax.tree.structure(laid) != jax.tree.structure(M.abstract_params(spec)):
        raise ValueError("the program's parameter tree is not the one the "
                         "benchmark lays the reference's weights into")

    def init_train_state(rng, spec, cfg, model=cfg):
        # ``build`` passes the run's RunConfig as ``cfg``
        return O.init_state(to_program(ref.init(rng, model)), cfg.param_dtype)

    class Feed:
        def __init__(self, spec, data_cfg):
            pass

        def batch_at(self, step):
            return generate.lm_batch(cell.seed, step, batch, seq,
                                     cfg["vocab_size"])

    window = Window(cell.seconds, int(traffic["trace_steps"]), tools)
    capture = Capture(T.build, key, cfg, opt["b1"])
    with patched(T, SyntheticLM=Feed, init_train_state=init_train_state,
                 Prefetcher=window.prefetcher(T.Prefetcher),
                 build=capture.build):
        out = T.train_loop(args, spec)
    device = harness.device_info(devices)
    losses = list(out.losses)
    del out
    gc.collect()

    span = window.end - window.start
    tokens = window.steps * batch * seq
    metrics = {"setup_s": window.start - tools.t_start,
               "train_tokens_per_s": tokens / span}
    obs = {"window_s": span, "steps": window.steps,
           "flops_per_token": flops.train_flops_per_token(cfg, seq),
           "compiles_in_window": tools.compiles.compiles_between(
               window.start, window.end)}

    want = ref.train3(cfg, opt, key,
                      [generate.lm_batch(cell.seed, i, batch, seq,
                                         cfg["vocab_size"]) for i in range(WARM)])
    checks, where = compare_run(losses[:WARM], capture.grad, capture.change, want)
    bad = int(np.sum(~np.isfinite(losses)))
    return harness.Outcome(
        attempted=window.steps + WARM, failed=bad, metrics=metrics, obs=obs,
        checks=checks, device=device,
        notes={"losses": losses[:WARM], "reference_losses": want["losses"],
               "worst_leaves": where,
               "compiles_in_window": obs["compiles_in_window"]})


def compare_run(losses, grad, change, want) -> tuple[list, dict]:
    """The three compared numbers, each beside its limit."""
    floor = np.median(list(want["grad"].values()))
    quiet = {k for k, v in want["grad"].items() if v < QUIET_LEAF * floor}
    g, g_at = compare.leaf_gap(grad, want["grad"])
    c, c_at = compare.leaf_gap(change, want["change"], skip=quiet)
    checks = [("loss_rel_gap", compare.loss_gap(losses, want["losses"]), LOSS_RTOL),
              ("grad_norm_gap", g, GRAD_GAP),
              ("change_norm_gap", c, CHANGE_GAP)]
    return checks, {"grad": g_at, "change": c_at, "quiet": sorted(quiet)}

