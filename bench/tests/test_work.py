"""The work counts against hand counts."""
from bench.work import kmeans, simjoin


def test_kmeans_work_by_hand():
    # N=10 points, D=3, K=2, one iteration: distances 10*2*(3 mul + 3 add)
    # = 120, the distance form's own and the update's 3*10*3 = 90
    assert kmeans.work(10, 3, 2, 1) == {"flops": 210.0, "bytes": 120.0}
    assert kmeans.work(10, 3, 2, 4) == {"flops": 840.0, "bytes": 480.0}


def test_kmeans_work_at_cell_size():
    w = kmeans.work(2458285, 68, 1024, 10)
    assert w["flops"] == (2 * 2458285 * 1024 * 68 + 3 * 2458285 * 68) * 10
    assert w["bytes"] == 2458285 * 68 * 4 * 10


def test_simjoin_work_by_hand():
    # 100 points of 3 f32 (1200 bytes) read, 7 pairs of two int32 written
    assert simjoin.work(100, 3, 7) == {"flops": 0.0, "bytes": 1256.0}
