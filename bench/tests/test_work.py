"""The byte and message counts of one V-cycle step against a hand count,
on a two-level Poisson hierarchy of a 2x2 grid."""
from bench import work

# the 5-point Poisson operator of a 2x2 grid: 4 rows, 4 diagonal and 8
# neighbour entries; two C-points, each F-point interpolating from both;
# the Galerkin coarse operator is a dense 2x2
LEVELS = [
    {"n": 4, "nnz": 12, "nc": 2, "nnz_r": 6, "nnz_p": 6,
     "msgs_a": 3, "msgs_r": 1, "msgs_p": 2},
    {"n": 2, "nnz": 4, "msgs_a": 5},
]
SOLVER = {"pre_degree": 1, "post_degree": 1, "coarse_degree": 2}


def test_vcycle_bytes_hand_count():
    # an application: 12 bytes a nonzero (f64 value, int32 index), 8 bytes
    # an input and an output entry
    a0 = 12 * 12 + 8 * (4 + 4)             # 208
    pre = a0 + 6 * 4 * 8                   # one step reads y, b, D^-1, x;
    post = pre                             # writes x, p
    resid = a0 + 3 * 4 * 8                 # r = b - A x
    restrict = 12 * 6 + 8 * (4 + 2)        # R r
    prolong = 12 * 6 + 8 * (2 + 4) + 3 * 4 * 8   # x += P e
    a1 = 12 * 4 + 8 * (2 + 2)
    coarse = 2 * a1 + (6 + 7) * 2 * 8      # the second step also reads p
    step = (a0 + 3 * 4 * 8) + 4 * 8 + 3 * 4 * 8  # b - A x, ||r||, x + v
    hand = pre + post + resid + restrict + prolong + coarse + step
    assert hand == 2240
    assert work.vcycle_bytes(LEVELS, SOLVER, 8) == hand


def test_halo_msgs_hand_count():
    # level 0's A: pre, post, the residual and the step's residual: 4
    # applications; the coarsest: 2 Chebyshev steps; R and P once
    assert work.halo_msgs(LEVELS, SOLVER) == 3 * 4 + 1 + 2 + 5 * 2


def test_halo_msgs_none_without_exchange():
    quiet = [dict(lv, msgs_a=0, msgs_r=0, msgs_p=0) for lv in LEVELS]
    assert work.halo_msgs(quiet, SOLVER) is None
