"""Test only: a second family. The program's one decoder, configured from
the key names other published configs use, cut in depth. The harness knows
it by its name in ``config.tiny-alt.json`` alone. Every function of a
family (``perfbench/families/gpt2.py`` lists them) is that family's, asked
with the keys translated."""

from perfbench.families import gpt2

_KEYS = {"vocab_size": "vocab_size", "n_positions": "max_position_embeddings",
         "n_embd": "hidden_size", "n_layer": "num_hidden_layers",
         "n_head": "num_attention_heads",
         "layer_norm_epsilon": "layer_norm_eps"}


def __getattr__(name):
    function = getattr(gpt2, name)

    def asked_in_its_keys(config_file, *args):
        model = config_file["model"]
        return function({"model": {k: model[own]
                                   for k, own in _KEYS.items()}}, *args)
    return asked_in_its_keys
