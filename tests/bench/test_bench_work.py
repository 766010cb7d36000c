"""Work counts and the peaks table the roofline and mfu divide by."""
import pytest

from benchmarks.chip import peaks, work

MNIST = work.Sizes(p=784, q=10, n=1020, layers=20, samples=60000, workers=20,
                   admm_iters=100)


def test_paper_mnist_train_is_about_4_8_tflop():
    # Propagation 2 n n_in J, Gram n(n+1)J, A 2QnJ, Cholesky M n^3/3 and
    # 2 Q n^2 per worker per ADMM iteration, over layers 0..20.
    prop = 2 * 1020 * 784 * 60000 + 19 * 2 * 1020 * 1020 * 60000
    gram = 784 * 785 * 60000 + 20 * 1020 * 1021 * 60000
    a = 2 * 10 * 60000 * (784 + 20 * 1020)
    chol = 20 * (784 ** 3 + 20 * 1020 ** 3) / 3
    admm = 100 * 20 * 2 * 10 * (784 ** 2 + 20 * 1020 ** 2)
    assert work.train_flops(MNIST) == pytest.approx(prop + gram + a + chol + admm)
    assert 4.7e12 < work.train_flops(MNIST) < 4.9e12


def test_symmetric_gram_counted_once():
    s = MNIST._replace(q=0)
    w = work.gram_stage(s, 0)   # layer 0: Gram of the inputs only
    assert w.flops == 784 * 785 * 3000 * 20
    assert w.flops < 2 * 784 * 784 * 3000 * 20


def test_gram_stage_bytes_are_inputs_once_and_outputs_once():
    w = work.gram_stage(MNIST, 2)
    m, j, n, q = 20, 3000, 1020, 10
    read = n * n + m * n * j + m * q * j
    written = m * n * j + m * n * n + m * q * n
    assert w.nbytes == 4 * (read + written)


def test_mesh_split_keeps_the_total():
    mesh = MNIST._replace(workers=4)
    assert work.train_flops(mesh) < work.train_flops(MNIST)
    # Same propagation and Gram work; fewer, larger workers factor less.
    assert work.gram_stage(mesh, 5).flops == work.gram_stage(MNIST, 5).flops


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peak_for("TPU v99")


def test_v5e_peaks_and_least_time():
    peak = peaks.peak_for("TPU v5 lite")
    assert peak.flops_per_s == 197e12 and peak.bytes_per_s == 819e9
    t, bound = peaks.least_time_s(197e12, 1.0, peak)
    assert t == pytest.approx(1.0) and bound == "flops"
    t, bound = peaks.least_time_s(1.0, 819e9, peak)
    assert t == pytest.approx(1.0) and bound == "bytes"
