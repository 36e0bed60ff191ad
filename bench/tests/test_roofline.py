"""Operation and byte counts checked against hand arithmetic at two shapes
each, the memory bound, and the peak table's refusal of an unknown
device."""
import pytest

from bench import roofline

@pytest.mark.parametrize("rows,layers,n,d,ops,nbytes", [
    # 16 x 4 x 5 x 5 x 11 candidates, one add and one min each;
    # bytes 16 x (4*5*11 + 4*25*8 + 4*5*11*8) = 16 x 2780
    (16, 4, 5, 11, 2 * 16 * 4 * 25 * 11, 16 * 2780),
    # 3 x 2 x 34 x 34 x 11; bytes 3 x (4*34*11 + 2*1156*8 + 2*34*11*8)
    (3, 2, 34, 11, 2 * 3 * 2 * 1156 * 11, 3 * (1496 + 18496 + 5984)),
])
def test_minplus_chain(rows, layers, n, d, ops, nbytes):
    assert roofline.minplus_chain(rows, layers, n, d) == (ops, nbytes)


def test_peaks_known_and_unknown():
    pk = roofline.peaks("TPU v5 lite")
    assert pk["flops_bf16"] == 197e12 and pk["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")


@pytest.mark.parametrize("nbytes,seconds", [(819e9, 1.0), (8.19e6, 1e-5)])
def test_memory_time(nbytes, seconds):
    pk = roofline.peaks("TPU v5 lite")
    assert roofline.memory_time(nbytes, pk) == pytest.approx(seconds,
                                                             rel=1e-15)


@pytest.mark.parametrize("quantize,ops,nbytes", [
    # h1: 3 states x 2 modes over 4 layers, h2: 2 states x 2 modes over 2
    # ops 2*6*4*25*11 + 2*4*2*25*11 = 13200 + 4400
    # bytes 6*(220 + 4*200 + 4*440) + 4*(220 + 2*200 + 2*440) = 16680 + 6000
    ("floor", 17600, 22680),
    # one mode: half the chains
    ("ceil", 8800, 11340),
])
def test_fin_relax_sums_apps(quantize, ops, nbytes):
    cfg = {"network": {"layout": [["mobile", 1], ["edge", 3],
                                  ["cloud", 1]]},
           "solver": {"gamma": 10, "quantize": quantize},
           "apps": {"h1": {"model": "m5"}, "h2": {"model": "m3"}},
           "models": {"m5": {"blocks": [[1, 1]] * 5},
                      "m3": {"blocks": [[1, 1]] * 3}}}
    assert roofline.fin_relax(cfg, {"h1": 3, "h2": 2}) == (ops, nbytes)
