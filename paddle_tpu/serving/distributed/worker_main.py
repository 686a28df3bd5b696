"""Process entry for one multi-host serving worker.

    python -m paddle_tpu.serving.distributed.worker_main \
        --role decode --engine paged --model gpt_tiny --seed 2024 \
        --engine-config '{"slots": 2, "max_len": 64}' \
        --endpoint-file /tmp/dec0.ep [--ckpt DIR] [--version 1]

Every worker of a deployment builds the SAME weights (identical seed →
identical init; or `--ckpt` loads a committed checkpoint), binds an
OS-assigned port, publishes `host:port` atomically through
`--endpoint-file`, and serves until a client sends OP_STOP.

Env integration (all inherited by fork/spawn, so chaos tests and trace
assertions drive workers without bespoke plumbing):
  PTN_TRACE_EXPORT_DIR  start a profiler and export a chrome trace on
                        shutdown (worker_name = <role><index>) — the
                        per-process half of the cross-host trace merge
  PTN_FAULTS            arm fault sites at import (observability.faults)
"""
import argparse
import json
import os
import sys
import time


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--role", choices=("decode", "prefill"),
                   default="decode")
    p.add_argument("--engine", default="paged",
                   help="serving engine kind: dense|paged|spec|tp|pp|"
                        "spec_pp (tp/pp/spec_pp serve this process's "
                        "whole local device grid — one process = one "
                        "worker GROUP)")
    p.add_argument("--model", default="gpt_tiny",
                   help="model factory name in paddle_tpu.text.models")
    p.add_argument("--seed", type=int, default=2024,
                   help="global seed BEFORE model build — every worker "
                        "of a deployment must agree (or pass --ckpt)")
    p.add_argument("--engine-config", default="{}",
                   help="JSON ctor kwargs for the engine config")
    p.add_argument("--serving-config", default="{}",
                   help="JSON ctor kwargs for ServingConfig (decode role)")
    p.add_argument("--endpoint-file", required=True)
    p.add_argument("--ckpt", default=None,
                   help="committed checkpoint dir to load initial "
                        "weights from (overrides seeded init)")
    p.add_argument("--version", type=int, default=0)
    p.add_argument("--index", type=int, default=0,
                   help="worker index (trace export naming only)")
    p.add_argument("--step-interval", type=float, default=0.0,
                   help="decode-step pacing in seconds (test/chaos knob)")
    args = p.parse_args(argv)

    import paddle_tpu
    from paddle_tpu.framework import compile_cache
    from paddle_tpu.serving import ServingConfig, make_engine
    from paddle_tpu.serving.distributed.worker import (
        ServingWorker, load_checkpoint_params)
    from paddle_tpu.text import models as _models

    compile_cache.place()
    prof = None
    trace_dir = os.environ.get("PTN_TRACE_EXPORT_DIR")
    if trace_dir:
        from paddle_tpu.profiler import Profiler, export_chrome_tracing
        prof = Profiler(timer_only=True,
                        on_trace_ready=export_chrome_tracing(
                            trace_dir,
                            worker_name=f"{args.role}{args.index}"))
        prof.start()

    paddle_tpu.seed(args.seed)
    model = getattr(_models, args.model)()
    model.eval()
    if args.ckpt:
        from paddle_tpu.core.tensor import Tensor
        params = load_checkpoint_params(args.ckpt)
        model.set_state_dict({k: Tensor(v) for k, v in params.items()})

    engine = make_engine(model, args.engine,
                         json.loads(args.engine_config))
    if args.engine in ("pp", "spec_pp"):
        # host-side model materialization (ROADMAP item 4d): the pp
        # engines keep their master copy host-resident and place
        # per-stage shards themselves, so the eager Layer's default-
        # device param copies are freed right after engine construction
        # — engine hbm_accounting() is now the WHOLE device story for a
        # bigger-than-one-host deployment (the Layer stays usable as
        # the hot-swap/state_dict source from host numpy). The spec_pp
        # draft Layer aliases the same device arrays through its OWN
        # Tensors and would keep them alive — free it too.
        from paddle_tpu.serving.distributed.pp import \
            free_eager_device_copies
        free_eager_device_copies(model)
        draft = getattr(engine, "draft_model", None)
        if draft is not None:
            free_eager_device_copies(draft)
    serving_cfg = ServingConfig(**json.loads(args.serving_config)) \
        if args.role == "decode" else None
    worker = ServingWorker(model, engine, role=args.role,
                           serving_config=serving_cfg,
                           version=args.version,
                           step_interval_s=args.step_interval)

    tmp = args.endpoint_file + ".tmp"
    with open(tmp, "w") as f:
        f.write(worker.endpoint)
    os.replace(tmp, args.endpoint_file)      # atomic publish

    worker.serve_until_stopped()
    if prof is not None:
        time.sleep(0.2)                      # let handler spans close
        prof.stop()                          # export the chrome trace
    return 0


if __name__ == "__main__":
    sys.exit(main())
