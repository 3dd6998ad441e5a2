"""Model families: what the harness knows of one architecture, found by the
`family` key of a configuration file, as its plain reference is.

A configuration `bench/configs/<name>.json` with `"family": "<family>"`
needs two files, and no edit to any other file of the harness:

`bench/families/<family>.py` (the system under test, and its counts):
    model_config(c, **override) -> repro ModelConfig
        `c` is the configuration file's dict (published key names);
        `override` sets run settings (`param_dtype`, `compute_dtype`,
        `remat`).  The program's weight tree for it must have exactly the
        leaves that the reference's `make_weights` makes
        (`program.check_tree`).
    train_flops_per_token(c, seq) -> int
        model FLOPs of one trained token of a row of `seq` (forward and
        backward, no recomputation): the train driver's `window_flops`.
    prefill_flops(c, n) -> int
        model FLOPs of one prompt of `n` tokens.
    decode_tick_flops(c, active, resident) -> int
        one decode tick of `active` slots holding `resident` tokens of
        cache between them before the tick.
    decode_tick_bytes(c, active, resident) -> int
        the least bytes that tick moves, in the served dtype.
    Count what this chip holds and computes: for sparse experts, the
    experts a token activates among those held here, never one dense MLP
    per layer.  A count that is too high reads a roofline or an MFU above
    100 %.

`bench/reference/<family>.py` (the plain reference; imports nothing of
the program):
    leaf_table(c) -> [(path, shape, init)]
        every weight leaf in a fixed order; per-layer leaves are stacked
        (L, ...) under `("blocks", ...)`, the rest sit at the top level.
    make_weights(c, seed, dtype, shardings=None) -> weight tree
        made on the device in one jitted call from the seed.
    make_leaf(c, seed, index, dtype, sharding=None) -> one leaf of it.
    Forward(c, weights, mode)  with  .logits(tokens, rows, pad_to)
        mode "f32" (HIGHEST precision) is the reference; "bf16", "fp8"
        are the serving controls.
    served_gaps(fwd, prompt, served, pad_to, judge=None) -> gap per token
    train_reference(c, seed, batches, hyper, mode, mesh, rows_used=1.0,
                    local_groups=1)
        -> {"losses", "grad_norms", "change_norms"}; norms keyed
        'blocks.<layer>.<path>' or '<path>'.  On a `mesh` no device may
        hold more than its share of a weight tree.  mode "bf16",
        `rows_used` < 1 and `local_groups` > 1 (no gradient exchange
        between that many data shards) are the training controls.

`bench.common.load_family` and `load_reference` import both by name.  A
family with no module is an error that names the missing file; nothing
falls back to another family's formulas.
"""
