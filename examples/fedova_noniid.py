"""FedOVA (Algorithm 2) vs FedAvg under pathological non-IID-2: each client
holds only two classes.  Reproduces the Fig. 3 behaviour on the synthetic
F-MNIST stand-in.

    PYTHONPATH=src python examples/fedova_noniid.py
"""
from repro.configs.base import FedConfig
from repro.configs.paper_models import FMNIST_CNN, reduced
from repro.data.synthetic import make_classification
from repro.fed.server import FederatedRun
from repro.utils.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    mcfg = reduced(FMNIST_CNN)
    train, test = make_classification(mcfg, n_train=1500, n_test=400,
                                      seed=0, noise=0.8)
    fcfg = FedConfig(num_clients=20, participation=0.25, local_epochs=2,
                     batch_size=16, rounds=8, noniid_l=2,
                     learning_rate=0.05, seed=0)
    results = {}
    for alg in ("fedavg_sgd", "fedova", "fedova_lbfgs"):
        run = FederatedRun(mcfg, fcfg, train, test, alg)
        print(f"== {alg} (each client sees only 2 of 10 classes) ==")
        hist = run.run(rounds=8, eval_every=4, verbose=True)
        results[alg] = max(h.get("accuracy", 0) for h in hist)
    print("\nbest accuracy:", results)


if __name__ == "__main__":
    main()
