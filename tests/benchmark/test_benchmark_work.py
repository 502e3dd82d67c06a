"""``work.py`` of the configuration against numbers counted by hand, and
the byte arithmetic the cells were sized by (ISSUE 23, Motivation)."""

from benchlib import config, load

alexnet_work = load("configs/alexnet/work.py")


def test_alexnet_macs_by_hand():
    macs = dict(alexnet_work.layer_macs(config("alexnet")))
    assert macs == {
        0: 55 * 55 * 96 * 11 * 11 * 3,          # conv1: 227 -> 55
        3: 27 * 27 * 256 * 5 * 5 * 96,          # conv2 after pool 55 -> 27
        6: 13 * 13 * 384 * 3 * 3 * 256,         # conv3 after pool 27 -> 13
        7: 13 * 13 * 384 * 3 * 3 * 384,
        8: 13 * 13 * 256 * 3 * 3 * 384,
        10: 6 * 6 * 256 * 4096,                 # fc6 after pool 13 -> 6
        12: 4096 * 4096,
        14: 4096 * 1000,
    }
    total = sum(macs.values())
    assert total == 1_135_256_096
    assert alexnet_work.forward_flops_per_image(config("alexnet")) \
        == 2 * total
    # backward: twice the forward, less conv1's input gradient
    assert alexnet_work.train_flops_per_image(config("alexnet")) \
        == 6 * total - 2 * macs[0] == 6_600_706_176


def test_alexnet_parameters_and_bytes():
    c = config("alexnet")
    assert alexnet_work.parameter_count(c) == (
        11 * 11 * 3 * 96 + 96 + 5 * 5 * 96 * 256 + 256
        + 3 * 3 * 256 * 384 + 384 + 3 * 3 * 384 * 384 + 384
        + 3 * 3 * 384 * 256 + 256 + 9216 * 4096 + 4096
        + 4096 * 4096 + 4096 + 4096 * 1000 + 1000) == 62_378_344
    assert alexnet_work.allreduce_bytes_per_step(c) == 249_513_376
    # 8,192 + 512 images of 227 x 227 x 3 float32: 618,348 B an image
    assert 227 * 227 * 3 * 4 == 618_348
    assert alexnet_work.dataset_bytes(c) == 5_382_100_992
    assert alexnet_work.dataset_bytes(c) > 5.0 * 2 ** 30
