"""Test only: a kernel's arithmetic added as a new file. One square matmul
of the model's width per matched event; compute-bound."""


def least_seconds(spec, facts, count, peak):
    cell = facts["cell"]
    shapes = cell["family"].attention_shapes(cell["config_file"])
    width = shapes["heads"] * shapes["head_dim"]
    return count * 2.0 * width ** 3 / peak["bf16_flops_per_s"]
