"""Quickstart: asynchronous personalized FL with EchoPFL in ~60 lines.

Twelve simulated mobile devices (mixed Jetson/RPi speed classes) train
personalized models on non-IID synthetic sensor data. The EchoPFL server
clusters them on the fly, aggregates every update (no stragglers dropped),
and broadcasts fresh cluster models on demand.

    PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.common.compile_cache import use_compile_cache
from repro.fl.experiment import build_clients, build_strategy
from repro.fl.simulator import Simulator


def main() -> None:
    use_compile_cache()
    # 1. a federated task: 12 devices, 4 latent user groups, non-IID labels
    task, clients, init_params = build_clients(
        "har", num_clients=12, seed=0, latent_clusters=4,
    )
    print(f"task={task.name}: {task.num_clients} clients, "
          f"{task.num_classes} classes, dim={task.dim}")

    # 2. the EchoPFL coordination server (the paper's contribution)
    server = build_strategy("echopfl", init_params, clients, seed=0)

    # 3. event-driven asynchronous simulation (virtual time, real training)
    sim = Simulator(clients, server, eval_interval=120.0, target_acc=0.85, seed=0)
    report = sim.run(max_time=1800.0)

    # 4. what happened
    print("\n-- result --")
    for k, v in report.summary().items():
        print(f"{k:22s} {v}")
    stats = server.stats()
    print(f"{'clusters':22s} {stats['clusters']}")
    print(f"{'broadcasts':22s} {stats['broadcasts']} "
          f"(rnn-decided: {stats['rnn_broadcasts']}, of {stats['decisions']} decisions)")
    print(f"{'staleness q_max':22s} {stats['staleness']['q_max']}")
    print(f"{'merges/expansions':22s} {stats['merges']}/{stats['expansions']}")

    acc = np.mean(list(report.per_client_acc.values()))
    assert acc > 0.5, "quickstart should comfortably beat random"
    print("\nOK: per-client personalized accuracy "
          f"{acc:.1%} (vs {1 / task.num_classes:.1%} random)")


if __name__ == "__main__":
    main()
