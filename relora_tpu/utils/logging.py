"""Process-0-aware logging and a metrics channel with an optional wandb backend.

The reference logs through loguru (console, rank 0 only — torchrun_main.py:371)
and wandb (torchrun_main.py:404-419, 918-943).  Neither package is a hard
dependency here: we use stdlib logging configured to be silent on non-zero
processes, and a `MetricsLogger` that writes JSONL locally and forwards to
wandb when it is importable and enabled.  The wandb metric schema (loss, lr,
update_step, tokens_seen, throughput_tokens/examples/batches, n_lora_restarts,
n_optimizer_resets) is preserved so dashboards port over unchanged.
"""

from __future__ import annotations

import json
import logging
import os
import sys
import threading
import time
from typing import Any, Mapping, Optional

_LOGGERS: dict[str, logging.Logger] = {}

# Set by the trainer right after jax.distributed.initialize(); must NOT be
# derived by calling into jax at import time — jax.process_index() initializes
# the XLA backend, which would make a later jax.distributed.initialize() on a
# multi-host launcher raise.
_PROCESS_INDEX: Optional[int] = None


def set_process_index(index: int) -> None:
    """Record this host's process index; non-zero hosts stop emitting INFO
    (parity: logger.remove() on nonzero ranks, torchrun_main.py:371)."""
    global _PROCESS_INDEX
    _PROCESS_INDEX = index


def _process_index() -> int:
    if _PROCESS_INDEX is not None:
        return _PROCESS_INDEX
    return int(os.environ.get("JAX_PROCESS_INDEX", "0"))


class _Process0Filter(logging.Filter):
    def filter(self, record: logging.LogRecord) -> bool:
        return _process_index() == 0 or record.levelno >= logging.ERROR


def get_logger(name: str = "relora_tpu") -> logging.Logger:
    """Stdlib logger that only emits on process 0, evaluated lazily at log
    time so importing this module never touches jax."""
    if name in _LOGGERS:
        return _LOGGERS[name]
    logger = logging.getLogger(name)
    if not logger.handlers:
        handler = logging.StreamHandler(sys.stderr)
        handler.setFormatter(
            logging.Formatter(
                "%(asctime)s | %(levelname)-7s | %(name)s:%(lineno)d | %(message)s",
                datefmt="%H:%M:%S",
            )
        )
        logger.addHandler(handler)
        logger.setLevel(logging.INFO)
        logger.addFilter(_Process0Filter())
        logger.propagate = False
    _LOGGERS[name] = logger
    return logger


_SAID_ONCE: set = set()


def info_once(logger: logging.Logger, message: str) -> None:
    """``logger.info`` the first time this exact message is seen.  For choices
    made at trace time (which attention arm a program was traced with): they
    recur per layer and per retrace, and a run's log needs each once."""
    if message not in _SAID_ONCE:
        _SAID_ONCE.add(message)
        logger.info(message)


class MetricsLogger:
    """Metrics sink: JSONL file always, wandb when available.

    Mirrors the reference's wandb usage: ``log(dict, step=global_step)``
    (torchrun_main.py:924-936), run-config capture (:639-655), and alerts
    (training_utils.py:397-404).
    """

    def __init__(
        self,
        run_dir: Optional[str] = None,
        project: str = "relora_tpu",
        run_name: Optional[str] = None,
        config: Optional[Mapping[str, Any]] = None,
        use_wandb: bool = False,
        resume_id: Optional[str] = None,
        source: Optional[str] = None,
    ):
        self.enabled = _process_index() == 0
        self.run_name = run_name
        self.run_id = resume_id
        # fleet series schema: when set, every record carries _source so the
        # FleetCollector / fleet_report can ingest this metrics.jsonl next to
        # scraped serving series (trainer passes "train"; serve.py passes its
        # replica id)
        self.source = source
        self._fh = None
        self._wandb = None
        # JSONL writes are line-atomic under this lock: the serving front-end
        # logs from its model thread while the event-loop thread logs
        # lifecycle events, and interleaved half-lines would corrupt the file
        self._lock = threading.Lock()
        if not self.enabled:
            return
        if run_dir is not None:
            os.makedirs(run_dir, exist_ok=True)
            self._fh = open(os.path.join(run_dir, "metrics.jsonl"), "a")
            if config:
                # offline equivalent of the wandb config capture
                # (torchrun_main.py:639-655): lets analysis tools (e.g.
                # plot_metrics.py scaling) read run hyperparams without wandb
                try:
                    with open(os.path.join(run_dir, "run_config.json"), "w") as f:
                        json.dump(dict(config), f, indent=2, default=str)
                except OSError as e:
                    get_logger().warning(f"could not write run_config.json: {e}")
        if use_wandb:
            try:
                import wandb  # type: ignore

                run = wandb.init(
                    project=project,
                    name=run_name,
                    config=dict(config) if config else None,
                    id=resume_id,
                    resume="allow" if resume_id else None,
                )
                self._wandb = wandb
                self.run_id = run.id
                self.run_name = run.name
            except Exception as e:  # wandb not installed / offline
                get_logger().warning(f"wandb unavailable ({e}); metrics go to JSONL only")

    def log(self, metrics: Mapping[str, Any], step: Optional[int] = None) -> None:
        if not self.enabled:
            return
        record = {k: _to_scalar(v) for k, v in metrics.items()}
        if step is not None:
            record["_step"] = step
        record["_time"] = time.time()
        if self.source is not None:
            record["_source"] = self.source
        with self._lock:
            if self._fh is not None:
                self._fh.write(json.dumps(record) + "\n")
                self._fh.flush()
        if self._wandb is not None:
            self._wandb.log(dict(metrics), step=step)

    def log_histograms(self, hists: Mapping[str, Any], step: Optional[int] = None) -> None:
        """wandb.watch-style histogram sink (torchrun_main.py:624-627):
        ``hists`` maps name -> (counts, bin_edges).  JSONL gets the raw
        arrays (offline dashboards re-render them); wandb gets native
        Histogram objects."""
        if not self.enabled or not hists:
            return
        import numpy as np

        record = {
            k: {
                "counts": np.asarray(counts).astype(int).tolist(),
                "edges": np.asarray(edges).astype(float).tolist(),
            }
            for k, (counts, edges) in hists.items()
        }
        if step is not None:
            record["_step"] = step
        record["_time"] = time.time()
        with self._lock:
            if self._fh is not None:
                self._fh.write(json.dumps(record) + "\n")
                self._fh.flush()
        if self._wandb is not None:
            self._wandb.log(
                {
                    k: self._wandb.Histogram(
                        np_histogram=(np.asarray(counts), np.asarray(edges))
                    )
                    for k, (counts, edges) in hists.items()
                },
                step=step,
            )

    def event(self, kind: str, step: Optional[int] = None, **fields: Any) -> None:
        """Structured lifecycle event (preemption, emergency_checkpoint,
        loss_spike, rollback, save_failed, ...): a JSONL record with
        ``_event: kind`` so postmortem tools can grep the run's incident
        timeline out of the metric stream."""
        if not self.enabled:
            return
        get_logger().info(f"event {kind}: {fields}")
        record = {"_event": kind, **{k: _to_scalar(v) for k, v in fields.items()}}
        if step is not None:
            record["_step"] = step
        record["_time"] = time.time()
        if self.source is not None:
            record["_source"] = self.source
        with self._lock:
            if self._fh is not None:
                self._fh.write(json.dumps(record) + "\n")
                self._fh.flush()

    def alert(self, title: str, text: str) -> None:
        """Parity: wandb.alert on bad post-reset LR (training_utils.py:397-404)."""
        get_logger().warning(f"ALERT [{title}]: {text}")
        if self._wandb is not None:
            try:
                self._wandb.alert(title=title, text=text)
            except Exception:
                pass

    def finish(self) -> None:
        with self._lock:
            if self._fh is not None:
                self._fh.close()
                self._fh = None
        if self._wandb is not None:
            self._wandb.finish()


def _to_scalar(v: Any) -> Any:
    try:
        import numpy as np

        if hasattr(v, "item") and getattr(v, "ndim", 1) == 0:
            return v.item()
        if isinstance(v, (np.floating, np.integer)):
            return v.item()
    except Exception:
        pass
    return v if isinstance(v, (int, float, str, bool, type(None), list)) else str(v)


def metrics_logger(**kwargs) -> MetricsLogger:
    return MetricsLogger(**kwargs)


#: where the persistent compilation cache lives when JAX_COMPILATION_CACHE_DIR
#: is not set: one fixed, git-ignored directory inside the checkout (the path
#: is part of the cache key, so a directory that moves never hits)
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_compile_cache",
)


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for this process and return
    the directory in use, so a restarted trainer or server loads its step
    programs from disk instead of compiling them again.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and nothing
    is set here; otherwise the cache goes to :data:`COMPILE_CACHE_DIR`.  Call
    before the first jax computation.  ``jax_enable_compilation_cache`` (JAX's
    own switch) still turns the whole thing off — the CPU test suite does.
    """
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    import jax

    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR
