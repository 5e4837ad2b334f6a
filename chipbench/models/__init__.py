"""Model modules, one file per architecture a configuration file names
under ``"model"``: the only place where the benchmark knows an
architecture.

Each file defines:

* ``dims(conf, *, use_lsh)``: the sizes and settings its plain reference
  reads from the configuration file;
* ``init_params(key, dims)``: the seeded weights, in the program's
  layout and types;
* ``loss_fn(params, tokens, labels, dims, groups, precision="f32",
  fault="")``: the plain reference's loss, with the float8 control
  (``precision="fp8"``) and the planted faults ``half_batch`` and
  ``no_exchange`` (``chipbench/calibrate.py``);
* ``train_flops_per_token(config, seq_len)``: model FLOPs of one trained
  token, the numerator of ``mfu``;
* ``PROGRAM_KEYS``: each key of the file's ``config`` mapped to how the
  program's ``ModelConfig`` states it (``program.model_config`` refuses a
  run where they differ);
* ``CUTS``: the ``reduced`` keys that a cut can change, and how the
  program's config takes each;
* ``FILE_ONLY``: the ``config`` keys the program has no field for, each
  with its reason.

A later module imports from an earlier one what it shares.
"""
