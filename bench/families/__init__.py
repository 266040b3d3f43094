"""Model families: the model-specific half of the benchmark.

A configuration file names its family (``"family": "dense"``), and
``spec.family(cfg)`` loads ``bench/families/<family>.py`` by path.  A
new architecture arrives as a new file here; the harness calls only the
functions below, each taking the configuration file's dict ``cfg``.

- ``dims(cfg)``: sizes under short names; the harness reads ``V``, the
  vocabulary.
- ``program_config(cfg)``: the program's ``ModelConfig`` for the file.
- ``make_params(cfg, seed, dtype=bf16)``: the base weights from the
  seed, on the device in one jitted call, in the program's tree layout.
- ``make_adapter(cfg, seed, tenant)``: tenant ``tenant``'s LoRA tree,
  float32.
- ``lora_targets(cfg)``: the adapted projections, each with its
  ``(in, out)`` shape.
- ``param_count(cfg)``: distinct base parameters this chip holds.
- ``served_gaps(params, adapter, prompt, tokens, pad_to, cfg,
  quant=None)``: the plain float32 reference, teacher-forced; for each
  served token its gap below the reference's best logit, and the gaps
  of the tokens the ``quant="fp8"`` control would put first.
- ``train_steps(params, adapter, batches, cfg, opt, quant=None)``: the
  reference's train steps (AdamW as ``opt`` states it); returns each
  step's loss, the first clipped gradient and the adapter after them.
- ``prefill_ops(cfg, prompt_lens)``, ``decode_ops(cfg, contexts)``,
  ``train_ops(cfg, rows, seq)``: operations the live work needs, for
  the ``mfu.*`` metrics.
- ``paged_attention(cfg, contexts)``: ``{"ops", "bytes"}`` the paged
  decode kernel needs for live rows, for its roofline.

The weights, the reference and the counts cover what this chip
computes: a family whose experts are shared out over chips makes,
checks and counts only the experts the chip holds.  The reference
imports nothing of the program and takes none of its arrays.
"""

INTERFACE = ("dims", "program_config", "make_params", "make_adapter",
             "lora_targets", "param_count", "served_gaps", "train_steps",
             "prefill_ops", "decode_ops", "train_ops", "paged_attention")
