"""Operation and byte counts against hand counts at the published widths."""
import pytest

from bench import flops

HAR = {"window": 60, "channels": 3, "n_classes": 12, "conv1": 32,
       "conv2": 64, "kernel": 5, "hidden": 128, "gen_hidden": 128}
BEARING = {"window": 120, "channels": 1, "n_classes": 10, "conv1": 32,
           "conv2": 64, "kernel": 7, "hidden": 128, "gen_hidden": 128}


@pytest.mark.parametrize("model, want", [
    (HAR, 57_600 + 614_400 + 245_760 + 3_072),
    (BEARING, 53_760 + 1_720_320 + 491_520 + 2_560),
])
def test_classifier_forward(model, want):
    assert flops.classifier_flops(model) == want


def test_generator_and_kmeans_hand_counts():
    # (16 + 2*3) x 128, 128 x 128, 128 x 180 multiply-adds
    assert flops.generator_flops(HAR) == 2 * (22 * 128 + 128 * 128
                                              + 128 * 180)
    # 3 channels x (5 distance passes x 60 x 12 x 6 + 4 x 2 x 60 x 12 x 2)
    assert flops.kmeans_flops(HAR, 12) == 3 * (5 * 60 * 12 * 6
                                               + 4 * 2 * 60 * 12 * 2)


def test_path_flops_follow_the_ladder():
    per = flops.path_flops(HAR, 12)
    corr = 2 * 12 * 60 * 3
    fwd = flops.classifier_flops(HAR)
    assert per[flops.D0] == per[flops.DEFER] == corr
    assert per[flops.D2] == corr + fwd
    assert per[flops.D3] == corr + flops.kmeans_flops(HAR, 12) + fwd
    assert per[flops.D4] == corr + flops.generator_flops(HAR) + fwd
    hist = [1, 0, 2, 3, 4, 5]
    assert flops.step_flops(HAR, 12, hist) == sum(
        h * per[d] for d, h in enumerate(hist))


def test_kernel_costs():
    sc = flops.signature_corr_cost(HAR, 3000)
    assert sc["bytes"] == 4 * (3000 * 180 + 12 * 180 + 3000 * 12)
    assert sc["flops"] == 2 * 3000 * 12 * 180 + 4 * 3012 * 180
    fq = flops.fake_quant_cost(HAR, 3000)
    elems = (480 + 10_240 + 122_880 + 1_536
             + 3000 * (180 + 960 + 960))
    assert fq["bytes"] == 8 * elems and fq["flops"] == 4 * elems
    peak = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    t, bound = flops.least_seconds(sc, peak)
    assert bound == "memory" and t == pytest.approx(sc["bytes"] / 819e9)
    t, bound = flops.least_seconds({"flops": 197e12, "bytes": 1.0}, peak)
    assert bound == "compute" and t == pytest.approx(1.0)
