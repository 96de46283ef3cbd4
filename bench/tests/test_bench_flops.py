"""The benchmark's operation and byte counters against hand counts."""
from bench import flops


def test_mlp_parameters():
    # 32*64 + 64 + 64*10 + 10
    assert flops.mlp_params(32, 64, 10) == 2762


def test_mlp_flops_per_trajectory_round():
    # forward 2*(32*64 + 64*10) = 5376 a sample, times 3 with the backward
    # pass, 32 samples a step; SGD 2 FLOPs a parameter; 100 clients x 5 steps
    per_step = 3 * 5376 * 32 + 2 * 2762
    assert flops.mlp_train_flops_per_traj_round(
        dim=32, hidden=64, classes=10, clients=100, local_steps=5,
        batch=32) == 100 * 5 * per_step
