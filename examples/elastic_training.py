"""Elastic training: DPM-driven scale-down/up via checkpoint-reshard.

Runs with 8 simulated devices (2 "pods" x 4) on CPU: trains a small model
on a 2-pod mesh, then a CloudPowerCap/DPM decision powers one pod off ->
the ElasticController checkpoints, rebuilds a 1-pod mesh, restores the state
resharded, and training resumes; later the pod returns and we scale back up.
The loss curve is continuous across both transitions.

  python examples/elastic_training.py
"""

import os

os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                           + os.environ.get("XLA_FLAGS", ""))

import sys                                                  # noqa: E402
import tempfile                                             # noqa: E402

sys.path.insert(0, "src")

import jax                                                  # noqa: E402
from jax.sharding import (AxisType, Mesh, NamedSharding,    # noqa: E402
                          PartitionSpec as P)

from repro import configs                                   # noqa: E402
from repro.checkpoint import Checkpointer                   # noqa: E402
from repro.data.pipeline import SyntheticTokens             # noqa: E402
from repro.optim.adamw import AdamW                         # noqa: E402
from repro.runtime.elastic import ElasticController         # noqa: E402
from repro.runtime.train_loop import (init_train_state,    # noqa: E402
                                      make_train_step)

BATCH, SEQ = 8, 64


def make_mesh(n_pods: int) -> Mesh:
    devs = jax.devices()[:n_pods * 4]
    return jax.make_mesh((len(devs),), ("data",),
                         axis_types=(AxisType.Auto,), devices=devs)


def make_shardings(mesh, target):
    # Replicated params, batch-sharded data (pure DP example).
    return jax.tree_util.tree_map(
        lambda _: NamedSharding(mesh, P()), target)


def batch_shardings(mesh):
    return NamedSharding(mesh, P("data", None))


def main():
    cfg = configs.get_smoke("granite_8b")
    opt = AdamW(learning_rate=3e-3)
    data = SyntheticTokens(cfg.vocab_size, SEQ, BATCH, seed=1)
    tmp = tempfile.mkdtemp(prefix="elastic_")
    ctl = ElasticController(Checkpointer(tmp), make_mesh, make_shardings)

    mesh = make_mesh(2)
    state = init_train_state(jax.random.PRNGKey(0), cfg, opt)
    step_fn = jax.jit(make_train_step(cfg, opt))

    def run_steps(mesh, state, n):
        losses = []
        with mesh:
            for _ in range(n):
                b = data.next_batch()
                batch = {"tokens": jax.device_put(b.tokens,
                                                  batch_shardings(mesh)),
                         "labels": jax.device_put(b.labels,
                                                  batch_shardings(mesh)),
                         "weights": jax.device_put(b.weights,
                                                   batch_shardings(mesh))}
                state, m = step_fn(state, batch)
                losses.append(float(m["loss"]))
        return state, losses

    print(f"phase 1: 2 pods ({mesh.devices.size} devices)")
    state, l1 = run_steps(mesh, state, 20)
    print(f"  loss {l1[0]:.3f} -> {l1[-1]:.3f}")

    print("DPM: low demand -> power off pod1; resize 2 -> 1 pods")
    mesh, state = ctl.resize(state, int(state.step), 2, 1, "dpm-poweroff",
                             {"data": data.state_dict()})
    print(f"phase 2: 1 pod ({mesh.devices.size} devices)")
    state, l2 = run_steps(mesh, state, 20)
    print(f"  loss {l2[0]:.3f} -> {l2[-1]:.3f}")
    assert l2[0] < l1[0], "training state survived the resize"

    print("DPM: demand spike -> power pod1 back on; resize 1 -> 2 pods")
    mesh, state = ctl.resize(state, int(state.step), 1, 2, "dpm-poweron")
    state, l3 = run_steps(mesh, state, 20)
    print(f"phase 3: 2 pods, loss {l3[0]:.3f} -> {l3[-1]:.3f}")
    assert l3[-1] < l1[0]
    print("resize history:", [(e.from_pods, e.to_pods, e.reason)
                              for e in ctl.history])
    print("OK: loss continuous across both elastic transitions")


if __name__ == "__main__":
    main()
