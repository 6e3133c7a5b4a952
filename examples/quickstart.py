"""Quickstart: train a federated classifier with the paper's FIM-L-BFGS
optimizer (Algorithm 1) and compare one round of accuracy against FedAvg.

    PYTHONPATH=src python examples/quickstart.py
"""
from repro.configs.base import FedConfig
from repro.configs.paper_models import FMNIST_CNN, reduced
from repro.data.synthetic import make_classification
from repro.fed.server import FederatedRun
from repro.utils.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    mcfg = reduced(FMNIST_CNN)  # paper CNN family, reduced for CPU
    train, test = make_classification(mcfg, n_train=1500, n_test=400,
                                      seed=0, noise=1.2)
    fcfg = FedConfig(num_clients=20, participation=0.25, local_epochs=1,
                     batch_size=10_000, rounds=16, noniid_l=3,
                     learning_rate=0.05, seed=0)

    for alg in ("fim_lbfgs", "fedavg_sgd"):
        run = FederatedRun(mcfg, fcfg, train, test, alg)
        print(f"== {alg} ==")
        run.run(rounds=16, eval_every=4, verbose=True)


if __name__ == "__main__":
    main()
