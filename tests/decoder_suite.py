"""The harness under the decoder reference suites
(`tests/test_<model>_reference.py`): a plain module, which pytest does not
collect. A suite is a `Suite` of its own data (the cell, the adapter whose
`reference` the benchmark's `correct` reads, the widths that make a layer
weigh in the stream, the kinds of parameter, its mixers, its wrong models
with the limit that catches each) and takes the shared cases by

    from decoder_suite import *  # noqa: F401,F403 — the shared cases
    SUITE = Suite(...)

Each shared case is stated once here, runs on the importing module's
`SUITE` and carries the cell's name in its id; `amp_run` and `float32_run`
build the cell's Programs once a module (once a worker that runs a case of
it). What only one model has stays in that model's file.

Run as a script on the attached TPU, outside any timed window, a suite's
file ends in `main(SUITE)`:

    python3 tests/test_<model>_reference.py readings[:wrong,wrong] [seed ...]   # program, wrong models (`fp8`: the fp8 reference) against the reference
    python3 tests/test_<model>_reference.py loads[/steps][@rate] [seed ...]   # held share by expert layer and over all of them from the fifth step on (what a benchmark window of steps - 4 steps reads as `moe_held_load_pct`) beside the steps' own device counts, the loss over a window's steps, a train step's counters (`falls`: the same)
    python3 tests/test_<model>_reference.py gradients[:wrong,wrong]   # at the published widths on one short row (no argument: the same), then against each wrong model named
"""

from __future__ import annotations

import os
import sys
from types import SimpleNamespace

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

__all__ = [
    "Suite", "caught", "pytest_generate_tests", "amp_run", "float32_run",
    "test_program_mixer_equals_reference",
    "test_whole_model_logits_and_loss_equal_reference_float32",
    "test_whole_model_equals_reference_under_bf16_amp",
    "test_a_wrong_model_is_caught",
    "test_one_train_steps_gradients_equal_jax_grad_of_the_reference",
]


# ------------------------------------------------------------- the helpers


def f32(tree):
    import jax

    return jax.tree.map(lambda v: np.asarray(v, np.float32), tree)


def highest(fn, *args, **kw):
    """`fn(*args, **kw)` as float32 with every product at the highest
    precision, primitive by primitive: a forward pass of a reference at
    the rehearsal size takes half a second so, and ten times that to
    compile."""
    import jax

    with jax.default_matmul_precision("highest"):
        return f32(fn(*args, **kw))


def compiled(fn, *args):
    """`fn(*args)` under `jax.jit` with every product at the highest
    precision: how a gradient, a token-a-step recurrence or a kernel under
    the interpreter is evaluated (primitive by primitive, `jax.grad` of a
    reference takes tens of seconds)."""
    import jax

    with jax.default_matmul_precision("highest"):
        return jax.jit(fn)(*args)


def rel(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.sqrt(np.mean((got - want) ** 2))
                 / (np.sqrt(np.mean(want ** 2)) + 1e-30))


def settled_counters():
    """`profiler.counters()` with the device counts of every step
    dispatched so far in it. `counters()` itself never waits for a step,
    and a step whose fetch the host has just read may still be
    microseconds from saying `is_ready()` of its counts."""
    from paddle_tpu import profiler

    with profiler._device_counts_lock:
        profiler._fold_device_counts(len(profiler._device_counts))
    return profiler.counters()


def state(names):
    import paddle_tpu as fluid

    scope = fluid.global_scope()
    return {n: np.array(scope.get(n), np.float32) for n in names}


def fp8(p):
    """The matrices rounded to fp8 (e4m3), the nearest precision below
    the bf16 the configuration states; norms' weights as they are."""
    import jax.numpy as jnp

    return {n: (np.asarray(jnp.asarray(v).astype(jnp.float8_e4m3fn).astype(
        jnp.float32)) if v.ndim >= 2 else v) for n, v in p.items()}


def expert_params(r, hidden, width, total, bias_scale=0.1):
    return {
        "m.moe.gate": r.randn(hidden, total).astype(np.float32) * 0.3,
        "m.moe.bias": r.randn(total).astype(np.float32) * bias_scale,
        "m.moe.w_gate": r.randn(total, hidden, width).astype(np.float32) * 0.2,
        "m.moe.w_up": r.randn(total, hidden, width).astype(np.float32) * 0.2,
        "m.moe.w_down": r.randn(total, width, hidden).astype(np.float32) * 0.2,
        "m.shared.gate.w_0": r.randn(hidden, width).astype(np.float32) * 0.2,
        "m.shared.up.w_0": r.randn(hidden, width).astype(np.float32) * 0.2,
        "m.shared.down.w_0": r.randn(width, hidden).astype(np.float32) * 0.2,
    }


def routed_shares(layer, hold, feed, shares, total, held):
    """Every share's (routed part, load) of an expert layer of `total`
    experts cut into `shares` of `held`, from one Program of four layers
    where stating all the shares in one took up to a minute and a half to
    compile. `layer(i)` states share `i` with its own `held_from` and
    returns its two outputs; `hold(i, order, lo)` gives layer `i` the
    router's columns in `order` and the experts `order[lo:lo + held]`.
    Three shares are stated so and in the experts' own order (the second,
    one in the middle, the last); every other share is layer 0, which
    holds the first `held`, run on the experts turned so that the share's
    stand first (the router's columns and bias with them: a top-k is
    indifferent to the order of its candidates)."""
    import paddle_tpu as fluid

    stated = (1, shares // 2 - 1, shares - 1)
    outs = [out for i in (0, *stated) for out in layer(i)]
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    for i in stated:
        hold(i, np.arange(total), i * held)
    routed, loads = [None] * shares, [None] * shares
    for i in range(shares):
        if i not in stated:
            hold(0, np.roll(np.arange(total), -i * held), 0)
            got = exe.run(feed=feed, fetch_list=outs)
            routed[i], loads[i] = got[:2]
    for at, i in enumerate(stated, 1):
        routed[i], loads[i] = got[2 * at:2 * at + 2]
    return routed, loads


ROUTED = ("router", "experts")


def check_gradients(got, want, before, limit, routed_limit=None, *, kinds):
    """Worst relative error by kind of parameter (`kinds`: {kind: the
    endings of its parameters' names}); `routed_limit` for the router and
    the experts, whose gradients change by a whole token's worth where
    rounding flips a selection (at 512 tokens an expert sees about 16). A
    gradient read as `before - after` carries float32's rounding of the
    parameter itself (6e-8 of a norm's weight of 1 under a gradient of
    1e-4), which is taken off the error before it is held to `limit`."""
    worst = {}
    for kind, endings in kinds.items():
        names = [n for n in want if n.endswith(endings)]
        assert names, kind
        for n in names:
            assert np.abs(want[n]).max() > 0, n
            rounding = 1.2e-7 * np.abs(before[n]).max()
            err = np.sqrt(np.mean((got[n] - want[n]) ** 2))
            err = max(err - rounding, 0.0) / np.sqrt(np.mean(want[n] ** 2))
            worst[kind] = max(worst.get(kind, 0.0), float(err))
    classed = {n for n in want if any(n.endswith(e) for e in kinds.values())}
    untrained = sorted(set(want) - classed)
    assert all(n.endswith(".moe.bias") for n in untrained), untrained
    for n in untrained:  # the router's correction is not the optimizer's
        assert not np.abs(got[n]).any(), n
    over = {k: v for k, v in worst.items()
            if v >= (limit if k not in ROUTED else routed_limit or limit)}
    assert not over, (over, worst)
    return worst


def guards():
    """Programs, names and a scope of their own."""
    import contextlib

    import paddle_tpu as fluid

    stack = contextlib.ExitStack()
    stack.enter_context(fluid.program_guard(fluid.Program(), fluid.Program()))
    stack.enter_context(fluid.unique_name.guard())
    stack.enter_context(fluid.scope_guard(fluid.Scope()))
    return stack


# what a float32 program is held to, and so what a lowering to bf16 has to
# exceed: float32's own rounding through a handful of layers reads 1e-6
FLOAT32_LIMITS = {"logits_rel_rms": 5e-5, "loss_abs": 1e-5}


def caught(float32=None, amp=None, **kw):
    """A wrong model of a suite's `wrong` table: `kw` is what the
    adapter's `reference` is called with, and the limits that catch it are
    `float32` times the float32 program's limit on the logits and `amp`
    times the cell's (0: refused by the cell's tolerance, by whichever
    limit; None: not asked of that program)."""
    return kw, float32, amp


# ---------------------------------------------------------------- a suite


class Suite:
    """One decoder's data, and what every suite does with its own."""

    def __init__(self, cell, adapter, *, kinds, wrong, as_at_width=None,
                 moved=None, mixers=(), mixer_program=None, want_mixer=None,
                 mixer_feeds=None, wrong_by_mixer=None, mixer_wrong_limit=0.02,
                 amp_loss_room=6.5, gradients_at=None, on_gradients=None,
                 seed=1, gradient_row=1024, checkpointed=None,
                 chip_kinds=None, chip_routed=(0.3, 0.6), reading_more=None,
                 steps=44, step_counters=(), gauges=(), rows_per_token=1):
        self.name, self.adapter, self.kinds = cell, adapter, kinds
        # the configuration at which a layer weighs in the residual stream
        # as at the published width, so that a wrong model shows
        self.as_at_width = dict(as_at_width or {})
        # which parameters (norms' weights, seeded 1) are moved off 1, so
        # that a norm left out or misplaced shows: a test of the name
        self.moved = moved
        # mixers: the names `mixer_program(which, model, batch, seq)`
        # builds (`u` in, the returned variable out) and
        # `want_mixer(which, p, feeds, model, wrong)` evaluates;
        # wrong_by_mixer: the wrong models a mixer alone already shows
        self.mixers, self.mixer_program = tuple(mixers), mixer_program
        self.want_mixer, self.mixer_feeds = want_mixer, mixer_feeds
        self.wrong_by_mixer = wrong_by_mixer or {}
        self.mixer_wrong_limit = mixer_wrong_limit
        self.wrong = wrong  # {name: caught(...)}
        # the rehearsal's loss is a mean of 96 bf16 per-token losses where
        # the cell's is one of 4,096: sqrt(4096 / 96) = 6.5 times as coarse
        self.amp_loss_room = amp_loss_room
        self.gradients_at = (self.as_at_width if gradients_at is None
                             else gradients_at)
        self.on_gradients = on_gradients
        # the script mode's: the default seed, the row the gradients are
        # compared on, the recurrence rebuilt a layer at a time in its
        # backward, the kinds as the chip can tell them apart, the limits
        # of the routed kinds (float32, AMP), a model's own words on a
        # reading, the steps of `loads` and the counters it prints
        self.seed, self.gradient_row = seed, gradient_row
        self.checkpointed, self.chip_kinds = checkpointed, chip_kinds or kinds
        self.chip_routed, self.reading_more = chip_routed, reading_more
        self.steps, self.step_counters, self.gauges = (
            steps, tuple(step_counters), tuple(gauges))
        # rows of a layer's input a token of the traffic (2 where a step
        # runs a noisy and a clean copy of every row)
        self.rows_per_token = rows_per_token

    def cell(self, rehearse=True, **config):
        from benchmark.harness import spec

        c = spec.cell(self.name, rehearse=rehearse)
        c["config"].update(config)
        return c["config"], c["traffic"]

    def move_norms(self, names, seed):
        import paddle_tpu as fluid

        if not self.moved:
            return
        scope, r = fluid.global_scope(), np.random.RandomState(seed)
        for n in names:
            if self.moved(n):
                scope.set(n, r.uniform(0.5, 1.5, np.shape(scope.get(n))).astype(
                    np.float32))

    def built_model(self, model, traffic, seed=3, place=None,
                    as_seeded=False):
        """Programs, executor and the seeded state by name, in the current
        scope (the caller holds the guards); the norms moved off 1 where
        the suite says which, unless the state is wanted `as_seeded`."""
        import paddle_tpu as fluid
        from benchmark.runners import train_loop

        main, startup, built, eval_prog = train_loop.build_programs(
            fluid, self.adapter, model, traffic, seed)
        exe = fluid.Executor(place or fluid.CPUPlace())
        exe.run(startup)
        names = [p.name for p in main.global_block().all_parameters()]
        if not as_seeded:
            self.move_norms(names, seed)
        return main, eval_prog, built, exe, names

    def batch_for(self, model, traffic, seed=0):
        return self.adapter.make_batch(np.random.RandomState(seed), model,
                                       traffic)

    def run(self, precision, seq_len=None):
        """The cell's program at the rehearsal size, built and run once:
        (model, batch, parameters, [loss, scored logits, the loss's terms
        where the model has them])."""
        model, traffic = self.cell(precision=precision, **self.as_at_width)
        if seq_len:
            traffic = dict(traffic, seq_len=seq_len)
        with guards():
            _, eval_prog, built, exe, names = self.built_model(model, traffic)
            batch = self.batch_for(model, traffic)
            got = exe.run(eval_prog, feed=batch,
                          fetch_list=built["check"] + built.get("terms", []))
            return model, batch, state(names), got

    def loss(self, p, batch, model, **kw):
        nll, count, _ = self.adapter.reference(p, batch, model, **kw)
        return nll / count

    def reference(self, run, **kw):
        """(loss, scored logits) of the adapter's reference on a run's
        state and batch."""
        model, batch, p, _ = run
        nll, count, want = highest(self.adapter.reference, p, batch, model,
                                   **kw)
        return nll / count, want[:self.adapter.SCORED_SEQUENCES]

    def check(self, run, **kw):
        from benchmark.runners import train_loop

        got = run[3]
        return train_loop.check_reference(
            got[0], got[1], *self.reference(run, **kw), self.adapter.TOLERANCE)

    def gradients(self, model, traffic, place=None, seed=3, **kw):
        """One train step (SGD at rate 1: the gradient is what a parameter
        lost) beside `jax.grad` of the reference's loss (of a wrong model's,
        with its `kw`) from the same seeded state and batch: `got`, `want`
        and `before` by name, `main`, `batch`, `model`, and what the step's
        trace `bumped`."""
        import jax

        import paddle_tpu as fluid
        from paddle_tpu import profiler

        model = dict(model, optimizer={"type": "SGD", "learning_rate": 1.0})
        c0 = profiler.counters()
        main, _, built, exe, names = self.built_model(model, traffic, seed,
                                                      place)
        before = state(names)
        batch = self.batch_for(model, traffic)
        exe.run(main, feed=batch, fetch_list=[built["loss"]])
        c1 = profiler.counters()
        got = {n: before[n] - v for n, v in state(names).items()}
        scope = fluid.global_scope()
        for n in list(scope.local_names()):  # the device is the reference's now
            scope.delete(n)
        want = f32(compiled(jax.grad(
            lambda p: self.loss(p, batch, model, **kw)), before))
        return SimpleNamespace(
            got=got, want=want, before=before, main=main, batch=batch,
            model=model, counters=c1,
            bumped=lambda n: c1.get(n, 0) - c0.get(n, 0))

    def mixer(self, which, batch=2, seq=80, seed=1, config=None):
        """One mixer alone in a Program, run on seeded rows: `got` (of
        `exe` fetching `y`), the parameters `p`, `want(wrong=())` of the
        reference's mixer, and what the run `bumped`."""
        import paddle_tpu as fluid
        from paddle_tpu import profiler

        model, _ = self.cell(
            **(self.as_at_width if config is None else config))
        y = self.mixer_program(which, model, batch, seq)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        names = [p.name for p in
                 fluid.default_main_program().global_block().all_parameters()]
        self.move_norms(names, 5)
        if self.mixer_feeds:
            feeds = self.mixer_feeds(which, model, batch, seq, seed)
        else:
            feeds = {"u": np.random.RandomState(seed).randn(
                batch, seq, model["hidden_size"]).astype(np.float32)}
        c0 = profiler.counters()
        (got,) = exe.run(feed=feeds, fetch_list=[y])
        c1 = profiler.counters()
        p = state(names)
        return SimpleNamespace(
            got=got, p=p, names=names, feeds=feeds, model=model, counters=c1,
            exe=exe, y=y,
            bumped=lambda n: c1.get(n, 0) - c0.get(n, 0),
            want=lambda wrong=(): self.want_mixer(which, p, feeds, model,
                                                  wrong))


# ------------------------------------------------------- the shared cases


def pytest_generate_tests(metafunc):
    """The cases below, imported into a suite's module, run on its `SUITE`
    and carry the cell's name."""
    if metafunc.function.__module__ != __name__:
        return
    suite = metafunc.module.SUITE
    also = {"which": suite.mixers, "wrong": list(suite.wrong)}
    over = [a for a in also if a in metafunc.fixturenames]
    if over:
        (arg,) = over
        metafunc.parametrize("suite," + arg, [(suite, v) for v in also[arg]],
                             ids=[f"{suite.name}-{v}" for v in also[arg]])
    else:
        metafunc.parametrize("suite", [suite], ids=[suite.name])


def kept(run_dir, name, build):
    """`build()`'s result, its device arrays as numpy, made once a run of
    the tests and kept in the run's own directory for the workers that
    come after: the
    driver's command spreads a module's cases over six processes, and
    each used to build and compile the module's Programs again (4 to 9 s
    a fixture, up to six times a run). A worker that finds the file
    being made waits for it."""
    import fcntl
    import pickle

    import jax

    path = run_dir / f"{name}.pickle"
    with open(run_dir / f"{name}.lock", "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        if path.exists():
            return pickle.loads(path.read_bytes())
        made = jax.tree.map(
            lambda v: np.asarray(v) if isinstance(v, jax.Array) else v,
            build())
        path.with_suffix(".tmp").write_bytes(pickle.dumps(made))
        path.with_suffix(".tmp").replace(path)
        return made


@pytest.fixture(scope="module")
def amp_run(request, run_dir):
    """The cell's program at the rehearsal size in the cell's precision,
    built and run once a run of the tests for the module's cases."""
    return kept(run_dir, request.module.__name__ + "-bf16_amp",
                lambda: request.module.SUITE.run("bf16_amp"))


@pytest.fixture(scope="module")
def float32_run(request, run_dir):
    """The same in float32, on rows of 80 tokens."""
    return kept(run_dir, request.module.__name__ + "-float32",
                lambda: request.module.SUITE.run("float32", seq_len=80))


def test_program_mixer_equals_reference(suite, which):
    """A mixer alone, 80 tokens in float32: the Program's is the
    reference's, and a mixer got wrong is no rounding of the right one."""
    m = suite.mixer(which)
    want = m.want()
    assert np.abs(want).max() > 1e-4  # something was computed
    assert rel(m.got, want) < 2e-5
    for wrong in suite.wrong_by_mixer.get(which, ()):
        assert rel(m.got, m.want((wrong,))) > suite.mixer_wrong_limit, wrong


def test_whole_model_logits_and_loss_equal_reference_float32(suite,
                                                             float32_run):
    model, batch, _, got = float32_run
    if "labels" in batch:  # a next-token model's; a denoiser has none
        np.testing.assert_array_equal(batch["labels"][:, :-1],
                                      batch["tokens"][:, 1:])
    rows, scored, vocab = np.asarray(got[1]).shape
    assert (rows, vocab) == (suite.adapter.SCORED_SEQUENCES,
                             model["vocab_size"])
    assert scored % (80 // suite.adapter.SCORED_EVERY) == 0
    check = suite.check(float32_run)
    assert check["ok"], check
    assert all(check[k] < v for k, v in FLOAT32_LIMITS.items()), check


def test_whole_model_equals_reference_under_bf16_amp(suite, amp_run):
    """The logits within the cell's limit, and the loss within the
    suite's room times the cell's (`Suite.amp_loss_room`)."""
    tolerance = suite.adapter.TOLERANCE
    check = suite.check(amp_run)
    assert 1e-4 < check["logits_rel_rms"] <= tolerance["logits_rel_rms"], check
    assert check["loss_abs"] <= suite.amp_loss_room * tolerance["loss_abs"], \
        check


def test_a_wrong_model_is_caught(suite, wrong, request):
    """The reference with its last layer left out, with one departure of
    the adapter's `WRONG`, or with one part lowered to bf16: against the
    float32 program it reads so many times that program's limit, against
    the program in the cell's precision it is refused, by the logits'
    limit with so much room (a mean of 96 bf16 losses is too coarse for
    the loss's limit to say much here)."""
    kw, float32, amp = suite.wrong[wrong]
    if float32 is not None:
        check = suite.check(request.getfixturevalue("float32_run"), **kw)
        assert (check["logits_rel_rms"]
                > float32 * FLOAT32_LIMITS["logits_rel_rms"]), (wrong, check)
    if amp is not None:
        check = suite.check(request.getfixturevalue("amp_run"), **kw)
        assert not check["ok"], (wrong, check)
        assert (check["logits_rel_rms"]
                >= amp * suite.adapter.TOLERANCE["logits_rel_rms"]), check


def test_one_train_steps_gradients_equal_jax_grad_of_the_reference(suite):
    """Every parameter's gradient, by the suite's kinds."""
    model, traffic = suite.cell(precision="float32", **suite.gradients_at)
    step = suite.gradients(model, dict(traffic, seq_len=80))
    worst = check_gradients(step.got, step.want, step.before, 2e-4,
                            kinds=suite.kinds)
    assert set(worst) == set(suite.kinds)
    if suite.on_gradients:
        suite.on_gradients(step)


# ------------------------------------------------------------ on the chip


def _reading(suite, seeds, only=(), few=2):
    """At the published widths on the attached TPU: the cell's own check
    (program in bf16 AMP against the float32 reference) at every seed,
    and the same program against the wrong models named in `only` at
    every seed (`fp8` among them: the fp8 reference), or with none named
    against each wrong model and the fp8 reference at the first `few`."""
    import paddle_tpu as fluid
    from benchmark.runners import train_loop

    adapter = suite.adapter
    model, traffic = suite.cell(rehearse=False)
    for at, seed in enumerate(seeds):
        with guards():
            _, eval_prog, built, exe, names = suite.built_model(
                model, traffic, seed, fluid.TPUPlace(), as_seeded=True)
            batch = suite.batch_for(model, traffic, seed)
            got_loss, got_logits = exe.run(eval_prog, feed=batch,
                                           fetch_list=built["check"])
            p = state(names)
        variants = [("reference", p, {})] + [
            ("fp8", fp8(p), {}) if w == "fp8" else (w, p, suite.wrong[w][0])
            for w in only]
        if not only and at < few:
            variants += [("fp8", fp8(p), {})] + [
                (w, p, kw) for w, (kw, _, _) in suite.wrong.items()]
        for label, params, kw in variants:
            loss, logits = train_loop.reference_outputs(
                adapter, params, batch, model, 1, **kw)
            check = train_loop.check_reference(
                got_loss, got_logits, loss, logits, adapter.TOLERANCE)
            more = suite.reading_more(got_logits, logits) \
                if suite.reading_more else ""
            print(f"seed {seed} {label}: logits_rel_rms "
                  f"{check['logits_rel_rms']:.5f}{more} loss_abs "
                  f"{check['loss_abs']:.5f} ok {check['ok']}", flush=True)


def _loads(suite, seeds, rate=None, steps=None):
    """At the published widths on the attached TPU, the cell's train step
    on the batches its runner would feed (one check batch drawn first,
    then the pool), `suite.steps` of them at `rate`: the share of a step's
    assignments that each expert layer's held experts take, at the first
    step, the window's first (the fifth) and the last, and the largest
    over all steps, beside the first block's share; the loss and its
    terms, and their fall as the runner takes it; the counters of the
    steps' traces."""
    import paddle_tpu as fluid
    from paddle_tpu import profiler

    adapter, steps = suite.adapter, steps or suite.steps
    model, traffic = suite.cell(rehearse=False)
    if rate:  # the sweep that chose the optimizer's rate
        model["optimizer"] = dict(model["optimizer"], learning_rate=rate)
    # a layer's assignments a step: Kimi's configuration spells the key
    # out, a dense model has neither
    total = (traffic["batch"] * traffic["seq_len"] * suite.rows_per_token
             * model.get("num_experts_per_tok",
                         model.get("num_experts_per_token", 0)))
    c0 = profiler.counters()

    def row(values):
        return " ".join(f"{v:.4f}" for v in values)

    for seed in seeds:
        counted = profiler.counters()
        with guards():
            main, _, built, exe, _ = suite.built_model(
                model, traffic, seed, fluid.TPUPlace(), as_seeded=True)
            rng = np.random.RandomState(seed)
            adapter.make_batch(rng, model, traffic)  # the check's batch
            pool = [adapter.make_batch(rng, model, traffic)
                    for _ in range(traffic["pool_batches"])]
            terms, loads = built.get("terms", []), built["loads"]
            shares, losses = [], []
            for i in range(steps):
                got = exe.run(main, feed=pool[i % len(pool)],
                              fetch_list=[built["loss"]] + terms + loads)
                losses.append([float(np.asarray(x).reshape(-1)[0])
                               for x in got[:1 + len(terms)]])
                shares.append([float(np.sum(x)) / total
                               for x in got[1 + len(terms):]])
        shares, losses = np.array(shares), np.array(losses)
        held = ""
        if loads:
            now = profiler.counters()
            rows = now["moe_block_rows"]
            # the steps' own device counts (ops/moe_ops.py) beside the
            # fetched loads: equal to the row, and `moe_held_load_pct` of
            # a benchmark window of `steps - 4` steps at this seed
            live, routed, blocks = (now.get(n, 0) - counted.get(n, 0) for n in (
                "moe_rows_live", "moe_rows_routed", "moe_blocks_run"))
            fetched = int(round(shares.sum() * total))
            held = (f"block {rows} rows = {rows / total:.4f} of {total}; "
                    f"held share by layer, step 0: {row(shares[0])}; step 4: "
                    f"{row(shares[4])}; step {steps - 1}: {row(shares[-1])}; "
                    f"largest: {row(shares.max(0))}; all layers, steps 4 to "
                    f"{steps - 1}: {100 * shares[4:].mean():.4f}%; the "
                    f"{steps} steps' own counts: moe_rows_live {live} "
                    f"(fetched loads {fetched}), moe_rows_routed {routed} "
                    f"(layers x steps x tokens x k {shares.size * total}), "
                    f"moe_blocks_run {blocks} of {shares.size}; ")
        fall = np.median(losses[4:14], 0) - np.median(losses[-10:], 0)
        print(f"seed {seed} rate {model['optimizer']['learning_rate']}: "
              f"{held}loss{' and its terms' if terms else ''}, step 0 "
              f"{row(losses[0])}, step 4 {row(losses[4])}, step {steps - 1} "
              f"{row(losses[-1])}; fall (median of steps 4-13 less median "
              f"of the last ten) {row(fall)}; every tenth: "
              + " ".join(f"{v:.3f}" for v in losses[::10, 0]), flush=True)
    c1 = profiler.counters()
    print("counters of", len(seeds), "train steps' traces:",
          {n: c1.get(n, 0) - c0.get(n, 0) for n in suite.step_counters},
          {n: c1.get(n) for n in suite.gauges}, flush=True)


def _chip_gradients(suite, only=()):
    """The gradients of every kind of parameter at the published widths,
    program against `jax.grad` of the reference, on one short row; then,
    in float32, against each wrong model named in `only`, whose reading is
    printed beside the limit and held to nothing."""
    import jax

    import paddle_tpu as fluid

    if suite.checkpointed:
        # How the reference is differentiated, not what it computes: the
        # token recurrence keeps its state a token for its backward (Kimi's
        # [32, 128, 128]: 12 GB over four layers of 512 tokens); rebuilt a
        # layer at a time it fits.
        setattr(suite.adapter, suite.checkpointed, jax.checkpoint(
            getattr(suite.adapter, suite.checkpointed)))
    model, traffic = suite.cell(rehearse=False, precision="float32")
    traffic = dict(traffic, seq_len=suite.gradient_row)
    # float32 on a TPU is a bf16 pass a product unless told otherwise, so
    # the "float32" program is held to 5%, the AMP one to 20%
    for precision, limit, routed in zip(("float32", "bf16_amp"), (0.05, 0.2),
                                        suite.chip_routed):
        with guards():
            step = suite.gradients(dict(model, precision=precision), traffic,
                                   place=fluid.TPUPlace())
        try:
            worst = check_gradients(step.got, step.want, step.before, limit,
                                    routed, kinds=suite.chip_kinds)
        except AssertionError as e:
            print(f"FAIL {precision}: {e}", flush=True)
            raise
        print(f"gradients at the published widths, s={suite.gradient_row}, "
              f"{precision}: worst relative error by kind "
              + ", ".join(f"{k} {v:.2e}" for k, v in worst.items()), flush=True)
    for w in only:
        with guards():
            step = suite.gradients(dict(model, precision="float32"), traffic,
                                   place=fluid.TPUPlace(), **suite.wrong[w][0])
        worst = check_gradients(step.got, step.want, step.before, np.inf,
                                kinds=suite.chip_kinds)
        print(f"gradients against the wrong model {w}, float32 (limit 5e-02): "
              + ", ".join(f"{k} {v:.2e}" for k, v in worst.items()), flush=True)


def main(suite, argv=None):
    """A suite's file run as a script: `readings[:wrong,...]`,
    `loads[/steps][@rate]` (or `falls`), `gradients[:wrong,...]` (or
    nothing), then seeds."""
    argv = sys.argv[1:] if argv is None else argv
    what, _, rate = (argv[0] if argv else "gradients").partition("@")
    what, _, steps = what.partition("/")
    what, _, only = what.partition(":")
    only = tuple(w for w in only.split(",") if w)
    unknown = [w for w in only if w not in suite.wrong
               and (w, what) != ("fp8", "readings")]
    if what not in ("readings", "loads", "falls", "gradients") or unknown:
        raise SystemExit(f"{what!r} {unknown}: readings[:wrong,...] (of "
                         f"{list(suite.wrong)}), loads[/steps][@rate], "
                         f"gradients[:wrong,...]")
    seeds = [int(a) for a in argv[1:]] or [suite.seed]
    import jax

    assert jax.devices()[0].platform == "tpu", jax.devices()
    if what == "readings":
        _reading(suite, seeds, only)
    elif what == "gradients":
        _chip_gradients(suite, only)
    else:
        _loads(suite, seeds, float(rate) if rate else None,
               int(steps) if steps else None)
