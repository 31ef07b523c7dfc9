"""A configuration's policy kind, found by name.

A configuration's ``policy.kind`` names a file ``policies/<kind>.py``,
looked up first under the cell's data directory and then under
``bench/policies/``, so that test data can bring a kind of its own.  The
file holds everything that differs by policy, and exports (see
``bench/README.md``):

* the reference, in plain ``jax.numpy`` (it imports nothing of the
  program): ``init_weights(key, config, dtype)``, the seed's weights as a
  pytree, and ``forward(weights, config, obs)``, Q-values ``(B,
  n_actions)``;
* the mapping onto the program, given what it needs by
  ``drive_actor_learner.py``: ``network_kwargs(config, constant)``,
  ``make_network``'s keyword arguments, checked against the program's
  constant that ``policy.constant`` names (None where it names none);
  ``to_program(weights, config)`` and ``from_program(tree, config)``, with
  the same leaf order on both sides;
* the operation counts (``opcount.work`` sums them):
  ``rollout_kernels(config, n_obs)`` and ``push_kernels(config, n_obs)``,
  ``{kernel: (ops, bytes, calls)}`` of the int8 kernels that one rollout
  step and one parameter push run over ``n_obs`` environments;
  ``push_flops(config, n_obs)``, the float operations of a push;
  ``learner_flops(config, batch)``, those of one learner update over
  ``batch`` rows.
"""
from __future__ import annotations

import importlib.util
import os
from typing import Dict

HERE = os.path.dirname(os.path.abspath(__file__))

CONTRACT = ("init_weights", "forward", "network_kwargs", "to_program",
            "from_program", "rollout_kernels", "push_kernels", "push_flops",
            "learner_flops")


class KindError(Exception):
    """A kind file that is missing or does not export the contract."""


def load(name: str, data_dir: str = HERE):
    """The module ``policies/<name>.py`` under ``data_dir``, else under
    ``bench/``."""
    for root in dict.fromkeys((data_dir, HERE)):
        path = os.path.join(root, "policies", f"{name}.py")
        if os.path.exists(path):
            break
    else:
        raise KindError(f"no policies/{name}.py under {data_dir} or {HERE}")
    spec = importlib.util.spec_from_file_location(f"bench_policy_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    missing = [f for f in CONTRACT if not callable(getattr(mod, f, None))]
    if missing:
        raise KindError(f"{path} does not export {missing}")
    return mod


class Policy:
    """A configuration bound to its kind: what ``check.py``,
    ``ActorLearnerDriver`` and ``opcount.work`` take in place of the
    configuration alone."""

    def __init__(self, config: Dict, kind):
        self.config, self.kind = config, kind

    def init(self, key):
        """The seed's float32 weights."""
        return self.kind.init_weights(key, self.config)

    def forward(self, weights, obs):
        """Reference Q-values ``(B, n_actions)``."""
        return self.kind.forward(weights, self.config, obs)

    def with_config(self, **changes) -> "Policy":
        """The same kind over a configuration with ``changes``."""
        return Policy(dict(self.config, **changes), self.kind)


def bind(config: Dict, data_dir: str = HERE) -> Policy:
    """``config`` bound to the kind its ``policy.kind`` names."""
    return Policy(config, load(config["policy"]["kind"], data_dir))
