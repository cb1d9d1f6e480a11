"""One module per architecture, named by a configuration's ``arch`` key.

A module ``bench/arch/<arch>.py`` describes its models to the rest of
the benchmark, which reads nothing else of them:

- ``describe(cfg) -> Model``: a frozen dataclass with at least ``arch``
  (the configuration's key), ``name``, ``d``, ``layers``, ``vocab``,
  ``vocab_rows``, ``tie``, ``eps`` and ``param_dtype``;
- ``layer_leaves(m, g)``: the leaves of global layer ``g``, each
  ``(path, shape, init, dtype)`` (``bench/model.Leaf``); a leaf's seed is
  keyed by ``(g, i)``, ``i`` its index in this list;
- ``shared_leaves(m)``: the embedding, head and final norm;
- ``decays(path, shape)``: AdamW's decay rule;
- ``layer_kind(m, g)``: a key that layers with the same equation share;
- ``layer(m, g, p, x, scales)``: the float32 reference forward of layer
  ``g``, which may depend on ``g`` only through ``layer_kind``;
- ``embed(m, table, tok, scale)`` and ``head_loss(m, s, x, labels,
  scales)``: the reference's embedding and loss;
- ``program_config(m)``: the program's ``ModelConfig`` for the model;
- ``model_flops_per_token(m, seq)`` and ``executed_matmul(m, traffic)``:
  the counts that ``mfu`` and ``matmul_roofline`` read.

``common.py`` holds the equations that architectures share.
"""
