"""Configurations: ``<name>.json`` holds the sizes as they are run,
``<name>.py`` builds the program's model from them. Found by name."""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Any, Callable, Optional

HERE = Path(__file__).resolve().parent


@dataclasses.dataclass
class Built:
    """What a configuration hands the harness."""

    loss_fn: Callable            # the program's loss over the program's model
    optimizer: Any               # optax transformation
    state_at: Callable           # key -> (params, model_state or None), f32;
    #                              traceable (see weights.seeded_tree)
    make_data: Callable          # (seed, n) -> (x, y) host arrays
    first_moment: Callable       # optimizer state -> its first-moment tree
    flops_per_sample: int
    input_dtype: Optional[Any]   # host-side cast of x (the pipeline's transform)
    loss_must_fall: bool

    def make_state(self, seed: int):
        """The seeded state, made on the device in one jitted call."""
        import jax

        from benchmark import weights

        return jax.jit(self.state_at)(weights.seed_key(seed))


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "benchmark_file_" + path.stem.replace("-", "_").replace(".", "_"),
        path,
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load(name: str, rehearse: bool = False) -> dict:
    """The configuration's sizes; in rehearsal, its tiny stand-ins."""
    cfg = json.loads((HERE / f"{name}.json").read_text())
    if rehearse:
        tiny = cfg["rehearsal"]
        cfg = {**cfg, **{k: v for k, v in tiny.items()
                         if k not in ("model", "limits")}}
        cfg["model"] = {**cfg["model"], **tiny.get("model", {})}
        cfg["limits"] = {**cfg["limits"], **tiny.get("limits", {})}
    return cfg


def build(name: str, cfg: dict) -> Built:
    return load_module(HERE / f"{name}.py").build(cfg)
