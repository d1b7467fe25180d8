"""One training cell's run, through ``SPMDTrainer.train`` and nothing else
of the program.

``train()`` owns its loop and hands back only its end state, so one
trainer is called twice. The first call takes the first three steps from
the seed's weights, on rows that all differ, and returns the parameters
after them: with the trainer's own step events (each step's loss, the
first gradient's norm as the optimizer got it) that is what the reference
is held against. The second call goes on from those parameters and is the
window: the trainer's flight recorder is the benchmark's tap on it. It
stamps every step, opens the window at the end of a warm-up step, and ends
the call (by raising through ``train()``) at the end of the first step
that ends ``--seconds`` later. Both calls trace and lower the same step
and fetch the same executable from the persistent cache.
"""

from __future__ import annotations

import gc
import math
import time

import numpy as np

from benchmark import check, serving, traffic


class WindowClosed(Exception):
    """Raised through ``SPMDTrainer.train`` to end the window's call."""


def make_recorder(tracer):
    from mmlspark_tpu.core.telemetry import FlightRecorder

    class WindowRecorder(FlightRecorder):
        """Step stamps, and the window's two edges on step boundaries."""

        warm_steps = seconds = None   # armed by the window's call
        t_open = t_close = None

        def arm(self, warm_steps: int, seconds: float) -> None:
            self.warm_steps, self.seconds, self.stamps = warm_steps, seconds, []

        def record(self, name, **kw):
            super().record(name, **kw)
            if name != "step" or self.warm_steps is None:
                return
            now = time.monotonic()
            self.stamps.append(now)
            if len(self.stamps) == self.warm_steps:
                self.t_open = now
                gc.collect()
                gc.freeze()
                gc.disable()
            elif self.t_open is not None:
                tracer.poll(self.t_open + self.seconds)
                if now >= self.t_open + self.seconds:
                    self.t_close = now
                    raise WindowClosed

    return WindowRecorder(capacity=1 << 16)


def run(fam, mix: dict, seed: int, seconds: float, trace_dir,
        clock, log) -> dict:
    import jax

    from mmlspark_tpu.train.trainer import SPMDTrainer, TrainConfig

    ref, sz = fam.reference, fam.sz
    spec = fam.cfg["program"]["trainer"]
    chips = math.prod(spec["mesh_axes"].values())
    rows, seq = int(mix["rows_per_chip"]) * chips, int(mix["seq"])
    check_steps, warm = int(mix["check_steps"]), int(mix["warm_steps"])
    steps = warm + math.ceil(seconds * float(mix["steps_per_s_ceiling"])) + 1
    x, y = traffic.train_batches(sz["v"], seed, rows * (check_steps + steps),
                                 seq)
    start = jax.device_get(serving.build_weights(fam, seed))
    clock.mark("weights")
    graph = serving.build_graph(fam)
    tracer = serving.Tracer(trace_dir)
    recorder = make_recorder(tracer)
    trainer = SPMDTrainer(graph, TrainConfig(
        epochs=1, batch_size=rows, learning_rate=spec["learning_rate"],
        optimizer=spec["optimizer"], log_every=1, shuffle=False,
        mesh_axes=dict(spec["mesh_axes"])), recorder=recorder)
    cut = rows * check_steps
    after = trainer.train(x[:cut], y[:cut], init_variables=start)
    first = [h for h in trainer.history if "loss" in h][:check_steps]
    clock.mark("check_steps")

    recorder.arm(warm, seconds)
    try:
        trainer.train(x[cut:], y[cut:], init_variables=after)
    except WindowClosed:
        pass
    finally:
        tracer.stop()
        gc.enable()
    if recorder.t_close is None:
        raise RuntimeError(
            f"the window's {steps} steps ran out before {seconds} s had "
            "passed: the traffic file's steps_per_s_ceiling is too low")
    clock.mark("window_call_warm_steps", at=recorder.t_open)
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices()[:chips])
    events = recorder.events()
    del trainer
    gc.collect()

    batches = [(x[i * rows:(i + 1) * rows], y[i * rows:(i + 1) * rows])
               for i in range(check_steps)]
    ref_run = check.reference_steps(ref, sz, seed, batches,
                                    spec["learning_rate"])
    numbers = check.train_gaps(
        ref, ref_run, [h["loss"] for h in first], first[0]["grad_norm"],
        fam.adapter.from_program(after, sz, np.stack))
    in_window = [e["t"] for e in events if e["name"] == "step"
                 and recorder.t_open < e["t"] <= recorder.t_close]
    return {"kind": "train", "seconds": seconds,
            "t_open": recorder.t_open, "t_close": recorder.t_close,
            "events": events, "peak_bytes": peak, "numbers": numbers,
            "attempted": len(in_window), "failed": 0, "chips": chips,
            "tokens_per_step": rows * seq, "seq": seq, "rows": rows,
            "trace_span": (tracer.t0, tracer.t1)}
