"""From a configuration file's published ``config.json`` keys to the
program's model fields; shared by the job kinds."""


def gpt2_fields(config_file: dict) -> dict:
    """``GPT2Config`` fields of a ``"family": "gpt2"`` configuration."""
    m = config_file["model"]
    if m.get("activation_function", "gelu_new") != "gelu_new":
        raise ValueError("the GPT-2 family uses gelu_new")
    return dict(vocab_size=m["vocab_size"], n_positions=m["n_positions"],
                n_embd=m["n_embd"], n_layer=m["n_layer"], n_head=m["n_head"],
                layer_norm_epsilon=m["layer_norm_epsilon"],
                activation="gelu", scan_layers=True)
