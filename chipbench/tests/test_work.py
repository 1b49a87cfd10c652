"""The work counts against hand arithmetic."""
from chipbench.families import kmeans, logreg


def test_kmeans_work():
    cfg = {"rows": 262144, "d": 3000, "estimator": {"k": 1000}}
    # 2 * 262,144 * 1,000 * 3,000 = 1.572864e12 FLOP an assignment pass
    assert kmeans.assign_flops(cfg) == 1.572864e12
    work = kmeans.fit_work(cfg, 30)  # 30 Lloyd passes and the inertia pass
    assert work["flops"] == 31 * 1.572864e12
    assert work["bytes"] == 31 * 262144 * 3000 * 4  # X read once a pass: 3.145728e9 bytes


def test_logreg_work():
    cfg = {"rows": 262144, "d": 3000}
    work = logreg.fit_work(cfg, 200)
    assert work["bytes"] == 200 * 3145728000  # one read of float32 X an iteration
    assert work["flops"] == 200 * 2 * 2 * 262144 * 3000  # forward and gradient matvec
    # bytes-bound on a v5e: 629 GB / 819 GB/s = 0.768 s against 0.0032 s of FLOP
    assert work["bytes"] / 819e9 > 100 * work["flops"] / 197e12
