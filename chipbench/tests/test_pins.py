"""``mnist.paper``'s compared numbers, pinned bit for bit: at ``TINY`` on
the CPU, for two experiment seeds, what ``check.numbers`` reads and what
the reference computes (its accuracy, T_k and E_k per round, and a hash
of every leaf of its final global and client models) are those the
harness gave before its model interface existed."""
import hashlib

import numpy as np
import pytest

from conftest import TINY

from chipbench import check, run

PINS = {
    1234: {
        "numbers": {
            "inputs_mismatch": "0x0.0p+0",
            "param_gap": "0x0.0p+0",
            "acc_mean_gap": "0x0.0p+0",
            "select_gap": "0x0.0p+0",
            "kmeans_gap": "0x0.0p+0",
            "T_gap": "0x1.d7de85ae86cffp-24",
            "E_gap": "0x1.b50e05c738296p-24",
        },
        "ref_accuracy": [
            "0x1.99999a0000000p-4",
            "0x1.ccccce0000000p-4",
            "0x1.6666660000000p-4",
        ],
        "ref_T": [
            "0x1.fe6ad3acd236ap-3",
            "0x1.fe6ad3acd236ap-3",
            "0x1.fe6ad3acd236ap-3",
        ],
        "ref_E": [
            "0x1.95ce52b4cf6dap-2",
            "0x1.8ccd87e742878p-3",
            "0x1.ae909248456fbp-3",
        ],
        "ref_global": {
            "b_c1": "b961f20ad3a1c38a",
            "b_c2": "c4057adfd0206d60",
            "b_fc1": "5745d095b32ced81",
            "b_fc2": "1010356e5d666b3c",
            "w_c1": "592d9eed487a156c",
            "w_c2": "6c514b18bdd8eef4",
            "w_fc1": "3e7818654b6b4717",
            "w_fc2": "5b008f2fe3ea187e",
        },
        "ref_clients": {
            "b_c1": "3db26979a972bec5",
            "b_c2": "b8fbacb3bc3dbf80",
            "b_fc1": "32b7e7fbf5a0b264",
            "b_fc2": "9ae6b1ff597c2c17",
            "w_c1": "4fc114590ebc10b2",
            "w_c2": "fd246bec016b9da7",
            "w_fc1": "220cd8c34c06bb67",
            "w_fc2": "9555a8f0788589e5",
        },
        "ref_features": "7fad9f038093a586",
        "ref_divergences": "5a1daa37e40aacc8",
    },
    987654321: {
        "numbers": {
            "inputs_mismatch": "0x0.0p+0",
            "param_gap": "0x0.0p+0",
            "acc_mean_gap": "0x0.0p+0",
            "select_gap": "0x0.0p+0",
            "kmeans_gap": "0x0.0p+0",
            "T_gap": "0x1.1c3e5e184bb46p-24",
            "E_gap": "0x1.2be1dfed77124p-26",
        },
        "ref_accuracy": [
            "0x1.0000000000000p-4",
            "0x1.0000000000000p-3",
            "0x1.99999a0000000p-4",
        ],
        "ref_T": [
            "0x1.6bf9c6fec9c07p-3",
            "0x1.5fcc0c7963ed7p-3",
            "0x1.5fcc0c7963ed7p-3",
        ],
        "ref_E": [
            "0x1.4b215e38f9f54p-2",
            "0x1.4e6fb39e0f1d2p-3",
            "0x1.50d34a0b6c243p-3",
        ],
        "ref_global": {
            "b_c1": "9e0c4cb8cc24e0e2",
            "b_c2": "d8775c80c8c7d582",
            "b_fc1": "ed0fc3e8113b2044",
            "b_fc2": "c150c32c5a52f24b",
            "w_c1": "0d66d8a84ed3015b",
            "w_c2": "1e1b92430080d9ae",
            "w_fc1": "5691f9e395512ab5",
            "w_fc2": "38e8a9b751e11296",
        },
        "ref_clients": {
            "b_c1": "710541433d9ed90d",
            "b_c2": "24a9b975de5c16ec",
            "b_fc1": "d132c735301c0c98",
            "b_fc2": "172b33f93894283c",
            "w_c1": "9535190d89dd3261",
            "w_c2": "780a0283497c3c8a",
            "w_fc1": "351c1a2508b3b863",
            "w_fc2": "d4e0e2a65fa185de",
        },
        "ref_features": "6f2e24a05903268f",
        "ref_divergences": "a64dc49c0b90e8b6",
    },
}


def sha(a) -> str:
    return hashlib.sha256(np.ascontiguousarray(a).tobytes()).hexdigest()[:16]


@pytest.fixture(scope="module")
def runner():
    cell = run.load_cell("mnist.paper")
    spec = run.spec_dict(cell, TINY)
    return cell, spec, run.Runner(spec, run.Spans())


@pytest.mark.parametrize("seed", sorted(PINS))
def test_mnist_paper_numbers_are_pinned(runner, seed):
    cell, spec, r = runner
    prog = run.program_outputs(r.run(seed), spec, cell["model"])
    res = check.numbers(prog, cell["model"], spec)
    ref = res["reference"]
    hexes = lambda xs: [float(x).hex() for x in xs]
    got = {"numbers": {k: float(v).hex() for k, v in res["numbers"].items()},
           "ref_accuracy": hexes(ref["accuracy"]), "ref_T": hexes(ref["T"]),
           "ref_E": hexes(ref["E"]),
           "ref_global": {k: sha(v) for k, v in ref["global"].items()},
           "ref_clients": {k: sha(v) for k, v in ref["clients"].items()},
           "ref_features": sha(ref["features"]),
           "ref_divergences": sha(ref["divergences"])}
    assert got == PINS[seed]
