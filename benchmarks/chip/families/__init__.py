"""Model families: one plug-in module per family, ``families/<name>.py``,
chosen by the ``reference`` key of a configuration file
(``spec.family``).

Everything the benchmark knows of one model's shape lives in its
plug-in; the rest of the harness (traffic, client, metrics, the
comparison that decides ``correct``) is the same for every family.  A
plug-in provides:

* ``TINY``: the configuration keys that shrink the model to a test size.
* ``program_config(conf)``: the program's ``ModelConfig`` for the
  configuration, after checking that the program can run it as stated.
* ``make_params(conf, seed)``: the parameters the program serves, drawn
  on the device from ``seed`` (``weights.key_for``) in one jitted call;
  ``published(params, conf)``: the same numbers in the published layout,
  which the reference reads.
* ``logits_at(params, conf, seqs, want, seq_len, arith)``: the plain
  float32 forward pass at ``HIGHEST`` over whole sequences, with every
  linear layer through ``reference.linear(x, w, arith)``; the logits at
  positions ``want[i]`` of ``seqs[i]``.  It imports nothing of the
  program.
* ``linears(conf)``: ``(name, K, N)`` of one layer's linears and the
  head's ``(K, N)``, at published widths;
  ``step_flops(conf, positions, context, tokens)`` and
  ``step_bytes(conf, positions, context)``: the least work of one window
  step (``work.py``).  ``metrics/crossbar_mac_roofline.py`` counts the
  crossbar's reads as ``linears``' layer once per layer, so a family
  whose layers differ needs a reader of its own for a crossbar cell.
"""
