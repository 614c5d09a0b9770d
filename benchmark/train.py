"""Runner for cells of kind ``train``: the whole donated step, fed a fresh
seeded batch every step, timed around ``block_until_ready`` on the loss.

Window: opens after the warm-up steps and closes at the end of the first
step that finishes at or after ``--seconds``; the rate is every token of
every step in it over its whole length, so no step is cut or dropped.
"""
import math
import time

from . import traffic
from .harness import median, say, within

# keys of a training mix's file that some code reads (prose apart)
KEYS = {"": {"batch", "warm_steps", "trace_seconds", "correct"},
        "batch": {"sequences", "tokens", "zipf_exponent", "pool"},
        "correct": {"first_loss_abs", "first_loss_reason"}}


def _loss_value(t):
    return float(t.numpy())


def run(run, fam, tracer, t_process):
    cfg, wl = run.cell.config, run.cell.workload
    tol = wl["correct"]
    t0 = time.perf_counter()
    hcg = fam.setup_parallel(cfg)
    model = fam.build_model(cfg, run.seed, hcg)
    step, place = fam.make_train_step(model, cfg, hcg)
    say(f"model and optimizer built in {time.perf_counter() - t0:.1f} s")

    seq, vocab = cfg["max_seq_len"], cfg["vocab_size"]
    spec = wl["batch"]
    t0 = time.perf_counter()
    ids, labels = traffic.zipf_batches(spec, vocab, seq, run.seed,
                                       int(spec["pool"]))
    batches = [(place(ids[i]), place(labels[i])) for i in range(len(ids))]
    run.tokens_per_step = int(spec["sequences"]) * seq
    run.flops_per_token = float(fam.train_flops_per_token(cfg))
    say(f"{len(batches)} batches of {spec['sequences']} x {seq} tokens on "
        f"the device in {time.perf_counter() - t0:.1f} s")

    # the plain reference's loss on the first batch, from the weights as
    # they are before the first step updates (and donates) them
    t0 = time.perf_counter()
    ref_loss = fam.reference.loss(fam.reference_weights(model),
                                  ids[0], labels[0])
    say(f"reference loss on batch 0: {ref_loss:.5f} "
        f"({time.perf_counter() - t0:.1f} s)")

    warm = []
    for i in range(1 + int(wl["warm_steps"])):
        t0 = time.perf_counter()
        warm.append(_loss_value(step(*batches[i % len(batches)])))
        say(f"warm step {i}: loss {warm[-1]:.5f} in "
            f"{time.perf_counter() - t0:.2f} s"
            + (" (compile or cache load included)" if i == 0 else ""))
    first_diff = abs(warm[0] - ref_loss)
    say(f"first-step loss {warm[0]:.5f} vs reference {ref_loss:.5f}: |diff| "
        f"{first_diff:.5f}, tolerance {tol['first_loss_abs']} "
        f"({tol['first_loss_reason']})")

    # ------------------------------------------------------- the window
    nxt = len(warm)
    t_open = time.perf_counter()
    run.setup_s = t_open - t_process
    tracer.start()
    t_prev = time.perf_counter()
    if tracer.on:
        t_open = t_prev       # the profiler's start is not a step's time
    while True:
        loss = _loss_value(step(*batches[nxt % len(batches)]))
        nxt += 1
        now = time.perf_counter()
        run.losses.append(loss)
        run.step_s.append(now - t_prev)
        t_prev = now
        if tracer.due(float(wl["trace_seconds"])):
            tracer.stop()
            t_prev = time.perf_counter()
        if now - t_open >= run.seconds:
            break
    tracer.stop()
    run.window_s = sum(run.step_s)
    if nxt > len(batches):
        say(f"the pool of {len(batches)} batches wrapped: {nxt} steps ran")

    steps = len(run.losses)
    tokens = steps * run.tokens_per_step
    run.e2e["train_tokens_per_s"] = tokens / run.window_s / run.chips
    run.attempted = steps
    run.failed = sum(not math.isfinite(x) for x in run.losses)
    k = 5 if steps >= 10 else max(1, steps // 3)
    head, tail = run.losses[:k], run.losses[-k:]
    falling = sum(tail) / k < sum(head) / k
    say(f"{steps} steps in {run.window_s:.3f} s: median step "
        f"{median(run.step_s) * 1e3:.2f} ms, loss {run.losses[0]:.4f} -> "
        f"{run.losses[-1]:.4f} (mean of first {k} {sum(head) / k:.4f}, of "
        f"last {k} {sum(tail) / k:.4f})")
    within(run, "first_loss_diff", first_diff, tol["first_loss_abs"])
    within(run, "losses_not_finite", run.failed, 0)
    # falling: the last steps' mean loss strictly under the first steps'
    within(run, "loss_last_over_first", sum(tail) / sum(head), 1.0)
    run.correct = (first_diff <= tol["first_loss_abs"] and run.failed == 0
                   and falling)
    if not run.correct:
        say(f"NOT correct: first-step diff ok "
            f"{first_diff <= tol['first_loss_abs']}, finite "
            f"{run.failed == 0}, falling {falling}")

    if tracer.on:
        _read_program(run, step)
        run.trace = tracer.summary(run.pallas_ops)


def _read_program(run, step):
    """Traced run only (it costs a trace and a cache load of the step):
    which instructions of the compiled step are Pallas kernels, that no
    kernel was demoted by a gate, and the compiler's own memory count."""
    from paddle_tpu.ops.pallas._common import gate_report
    from .trace_reduce import pallas_instructions
    t0 = time.perf_counter()
    jitted, args = step._last_exec
    compiled = jitted.lower(*args).compile()
    text = compiled.as_text()
    run.pallas_ops = pallas_instructions(text)
    mem = compiled.memory_analysis()
    gib = 2.0 ** 30
    say(f"compiled step: {len(run.pallas_ops)} Pallas custom calls "
        f"{sorted(run.pallas_ops)[:8]}; memory_analysis per device: "
        f"arguments {mem.argument_size_in_bytes / gib:.2f} GiB, temporaries "
        f"{mem.temp_size_in_bytes / gib:.2f} GiB, outputs "
        f"{mem.output_size_in_bytes / gib:.2f} GiB, aliased "
        f"{mem.alias_size_in_bytes / gib:.2f} GiB "
        f"({time.perf_counter() - t0:.1f} s)")
    say("collectives in the step: " + ", ".join(
        f"{op} x{text.count(op + '(') + text.count(op + '-start(')}"
        for op in ("all-reduce", "all-gather", "reduce-scatter",
                   "all-to-all", "collective-permute")))
    say(f"kernel gate verdicts: {gate_report() or 'none measured'} (flash "
        "attention serves unmeasured; no other Pallas kernel is on the "
        "training path)")
