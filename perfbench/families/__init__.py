"""One module per model family, named by a configuration file's ``family``:
what the jobs, the readers and the kernels' arithmetic take from a model
(``gpt2.py`` is the list of functions)."""
