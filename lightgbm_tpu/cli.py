"""Config-file command-line front end.

Analog of the reference CLI (``src/main.cpp`` + ``src/application/
application.cpp:209-281``): ``python -m lightgbm_tpu config=train.conf
[key=value ...]`` dispatches on ``task`` — train, predict, refit,
save_binary, convert_model — so the reference's shipped example configs
run unmodified.

Parameter precedence matches Application::LoadParameters
(application.cpp:31-86): command-line pairs beat config-file pairs;
within each source the first occurrence wins (KeepFirstValues).
"""

from __future__ import annotations

import os
import sys
from typing import Dict, List, Optional

import numpy as np

from .config import Config
from .io import parse_config_file

__all__ = ["main", "run", "serve"]

# IO/driver keys the training engine does not consume (output_model and
# snapshot_freq stay: engine.train writes periodic checkpoints)
_ENGINE_DROP = {
    "task", "data", "valid", "input_model", "output_result",
    "machine_list_filename", "local_listen_port", "save_binary",
    "two_round", "is_enable_sparse", "enable_bundle", "convert_model",
    "convert_model_language",
}


def _parse_argv(argv: List[str]) -> Dict[str, str]:
    params: Dict[str, str] = {}
    for tok in argv:
        if "=" not in tok:
            raise SystemExit(f"unrecognized argument (want key=value): "
                             f"{tok!r}")
        k, v = tok.split("=", 1)
        params.setdefault(k.strip(), v.strip())
    conf = params.pop("config", params.pop("config_file", None))
    if conf:
        base_dir = os.path.dirname(os.path.abspath(conf))
        for k, v in parse_config_file(conf).items():
            params.setdefault(k, v)
        params["_conf_dir"] = base_dir
    return params


def _resolve_path(path: str, conf_dir: Optional[str]) -> str:
    if os.path.isabs(path) or os.path.exists(path) or not conf_dir:
        return path
    cand = os.path.join(conf_dir, path)
    return cand if os.path.exists(cand) else path


def serve(params: Dict[str, str],
          conf_dir: Optional[str] = None) -> int:
    """task=serve: stand up the prediction server (serving/server.py)
    over one or more registered models. Serve-specific keys (port,
    max_batch_rows, ...) are not training parameters, so this path
    never builds a Config."""
    from .serving import ModelRegistry, PredictionServer

    spec = params.get("model") or params.get("input_model")
    if not spec:
        raise SystemExit("task=serve needs model=<model file> "
                         "(or model=name:file[,name:file...])")
    registry = ModelRegistry(
        warmup_rows=int(params.get("warmup_rows", 256)))
    truthy = ("1", "true", "yes", "on")
    server = PredictionServer(
        registry,
        host=params.get("host", "127.0.0.1"),
        port=int(params.get("port", 8080)),
        max_batch_rows=int(params.get("max_batch_rows", 1024)),
        max_wait_us=int(params.get("max_wait_us", 2000)),
        max_queue_rows=(int(params["max_queue_rows"])
                        if "max_queue_rows" in params else None),
        min_bucket=int(params.get("min_bucket", 16)),
        replicas=int(params.get("replicas", 0)),
        compiled_predict=(str(params.get("compiled_predict", ""))
                          .lower() in truthy),
        qps_budget=(float(params["qps_budget"])
                    if "qps_budget" in params else None))
    for item in str(spec).split(","):
        item = item.strip()
        if not item:
            continue
        name, sep, path = item.partition(":")
        if not sep:
            name, path = params.get("name", "default"), item
        mv = registry.register(name, _resolve_path(path, conf_dir))
        print(f"registered {mv.name} v{mv.version} "
              f"({mv.booster.num_trees()} trees) from {mv.source}")
    server._bind()
    print(f"serving on http://{server.host}:{server.port} — endpoints: "
          "/predict /models /models/swap /models/rollback /healthz "
          "/healthz/alive /healthz/ready /metrics")
    _install_drain_handler(server)
    server.serve_forever()
    # the drain runs on a helper thread (see _install_drain_handler);
    # wait for it so in-flight batcher work finishes before exit
    t = getattr(server, "_drain_thread", None)
    if t is not None:
        t.join(timeout=60)
        print("drained: in-flight work finished, exiting")
    return 0


def _install_drain_handler(server) -> None:
    """SIGTERM -> graceful drain. The handler runs on the main thread —
    the same thread blocked inside ``serve_forever`` — and
    ``httpd.shutdown()`` waits for that loop to exit, so the drain must
    run on a helper thread; ``serve_forever`` then returns and the
    process exits 0 once in-flight batcher work completes."""
    import signal
    import threading

    def _on_term(signum, frame):
        print("SIGTERM: draining (not-ready; finishing in-flight "
              "work)", flush=True)
        t = threading.Thread(target=server.drain, name="serve-drain",
                             daemon=True)
        server._drain_thread = t
        t.start()

    try:
        signal.signal(signal.SIGTERM, _on_term)
    except ValueError:
        pass  # not on the main thread (embedded use) — skip


def run(params: Dict[str, str]) -> int:
    import lightgbm_tpu as lgb

    # persistent XLA compile cache (engine.enable_compilation_cache):
    # CLI processes are one-shot, so without it every invocation repays
    # the full compile+warmup; with it only the first run of a checkout
    # does
    from .engine import enable_compilation_cache
    enable_compilation_cache()

    conf_dir = params.pop("_conf_dir", None)
    task = (params.get("task") or "train").strip()
    if task == "serve":
        return serve(params, conf_dir)
    cfg = Config({k: v for k, v in params.items()
                  if k not in ("valid",)})  # valid handled as list below
    engine_params = {k: v for k, v in params.items()
                     if Config.canonical_name(k) not in _ENGINE_DROP}

    if task in ("train", "refit"):
        data_path = _resolve_path(cfg.data, conf_dir)
        if not data_path:
            raise SystemExit("task=train needs data=<file>")
        train = lgb.Dataset(data_path, params=engine_params)
        if task == "refit":
            model_in = _resolve_path(cfg.input_model, conf_dir)
            base = lgb.Booster(model_file=model_in)
            train.construct()
            booster = base.refit(train._raw_data
                                 if train._raw_data is not None
                                 else data_path, train.label)
            booster.save_model(cfg.output_model)
            print(f"Finished refit; model written to {cfg.output_model}")
            return 0
        valid_sets, valid_names = [], []
        # any alias of `valid` names the validation files (config.py
        # registers test/test_data/valid_data/valid_data_file/...)
        vspec = next(
            (v for k, v in params.items()
             if Config.canonical_name(k) == "valid" and v), "")
        for i, v in enumerate(str(vspec).split(",")):
            v = v.strip()
            if not v:
                continue
            valid_sets.append(lgb.Dataset(_resolve_path(v, conf_dir),
                                          reference=train,
                                          params=engine_params))
            valid_names.append(f"valid_{i + 1}")
        if bool(cfg.save_binary):
            train.construct().save_binary(data_path + ".bin")
        callbacks = []
        if int(cfg.metric_freq) > 0 and int(cfg.verbosity) >= 0:
            callbacks.append(lgb.log_evaluation(int(cfg.metric_freq)))
        from .resilience import TrainingPreempted
        try:
            booster = lgb.train(
                engine_params, train,
                num_boost_round=int(cfg.num_iterations),
                valid_sets=valid_sets, valid_names=valid_names,
                callbacks=callbacks)
        except TrainingPreempted as e:
            # graceful preemption: the final checkpoint is on disk;
            # exit 0 so supervisors treat the eviction as clean
            print(f"Training preempted: {e}")
            print("Re-run with resume=auto to continue bit-identically.")
            return 0
        booster.save_model(cfg.output_model)
        print(f"Finished training; model written to {cfg.output_model}")
        return 0

    if task == "predict":
        model_in = _resolve_path(cfg.input_model, conf_dir)
        data_path = _resolve_path(cfg.data, conf_dir)
        booster = lgb.Booster(model_file=model_in)
        n_iter = int(cfg.num_iteration_predict)
        pred = booster.predict(
            data_path, raw_score=bool(cfg.predict_raw_score),
            pred_leaf=bool(cfg.predict_leaf_index),
            pred_contrib=bool(cfg.predict_contrib),
            start_iteration=int(cfg.start_iteration_predict),
            num_iteration=None if n_iter <= 0 else n_iter,
            pred_early_stop=bool(cfg.pred_early_stop),
            pred_early_stop_freq=int(cfg.pred_early_stop_freq),
            pred_early_stop_margin=float(cfg.pred_early_stop_margin))
        out = np.asarray(pred)
        with open(cfg.output_result, "w") as f:
            if out.ndim == 1:
                for v in out:
                    f.write(f"{v:.18g}\n")
            else:
                for row in out:
                    f.write("\t".join(f"{v:.18g}" for v in row) + "\n")
        print(f"Finished prediction; results written to "
              f"{cfg.output_result}")
        return 0

    if task == "save_binary":
        data_path = _resolve_path(cfg.data, conf_dir)
        ds = lgb.Dataset(data_path, params=dict(
            engine_params, _allow_no_label=True))
        ds.construct().save_binary(data_path + ".bin")
        print(f"Binary dataset written to {data_path}.bin")
        return 0

    if task == "convert_model":
        from .codegen import model_to_c
        model_in = _resolve_path(cfg.input_model, conf_dir)
        booster = lgb.Booster(model_file=model_in)
        code = model_to_c(booster._all_trees(),
                          num_class=max(1, booster._num_class),
                          objective=booster._objective_name,
                          average_output=booster._average_output)
        out_path = cfg.convert_model
        with open(out_path, "w") as f:
            f.write(code)
        print(f"Converted model written to {out_path}")
        return 0

    raise SystemExit(f"unknown task: {task!r}")


def main(argv: Optional[List[str]] = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv or argv[0] in ("-h", "--help"):
        print("usage: python -m lightgbm_tpu config=<file> [key=value ...]\n"
              "       python -m lightgbm_tpu serve model=<file> "
              "[port=8080 ...]\n"
              "       python -m lightgbm_tpu ingest data=<csv|npy|npz> "
              "out=<dir> [key=value ...]\n"
              "       python -m lightgbm_tpu trace-doctor [--config ...]"
              " [--mode ...]\n"
              "       python -m lightgbm_tpu chaos [--fast] [--cell ...]\n"
              "       python -m lightgbm_tpu monitor <run_dir|events."
              "jsonl> [--check] [--perf]\n"
              "tasks: train | predict | refit | save_binary | serve | "
              "ingest | trace-doctor | chaos | monitor")
        return 0
    # `python -m lightgbm_tpu serve model=...` — subcommand spelling of
    # task=serve (the reference CLI is key=value only; serve is ours)
    if argv[0] == "serve":
        argv = ["task=serve"] + argv[1:]
    # `ingest` — out-of-core shard construction (data/ingest.py):
    # stream a CSV/npy/npz through the mergeable quantile sketch and
    # write checksummed .lgbtpu shards the Dataset loader consumes
    if argv[0] == "ingest":
        params = _parse_argv(argv[1:])
        conf_dir = params.pop("_conf_dir", None)
        data = params.pop("data", None)
        out = params.pop("out", params.pop("out_dir", None))
        if not data or not out:
            raise SystemExit("ingest needs data=<file> out=<dir>")
        label = params.pop("label_file", None)
        from .data import ingest as run_ingest
        summary = run_ingest(
            _resolve_path(data, conf_dir), _resolve_path(out, conf_dir),
            params=params,
            label=_resolve_path(label, conf_dir) if label else None)
        print(f"Ingest complete: {summary['total_rows']} rows -> "
              f"{summary['num_shards']} shards in {summary['out_dir']} "
              f"({summary['shards_written']} written, "
              f"{summary['shards_reused']} reused)")
        return 0
    # `trace-doctor` — the static-analysis battery (analysis/doctor.py);
    # argparse-style flags, not key=value, so it dispatches before run()
    if argv[0] in ("trace-doctor", "trace_doctor"):
        from .analysis.doctor import doctor_main
        return doctor_main(argv[1:])
    # `monitor` — render a run-event log (telemetry/events.py) into a
    # phase/throughput/faults report; `--check` is the schema
    # self-check, `--perf` the profiler-capture phase tables
    if argv[0] == "monitor":
        from .telemetry.monitor import monitor_main
        return monitor_main(argv[1:])
    # `chaos` — the repo-checkout harness scripts/chaos_train.py
    # (fault injection + bit-identical recovery)
    if argv[0] == "chaos":
        import importlib.util
        here = os.path.dirname(os.path.abspath(__file__))
        path = os.path.join(os.path.dirname(here), "scripts",
                            "chaos_train.py")
        if not os.path.exists(path):
            raise SystemExit(
                "chaos harness not found (scripts/chaos_train.py ships "
                "with the repo checkout, not the installed package)")
        spec = importlib.util.spec_from_file_location("chaos_train", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.main(argv[1:])
    return run(_parse_argv(argv))


if __name__ == "__main__":  # pragma: no cover
    raise SystemExit(main())
