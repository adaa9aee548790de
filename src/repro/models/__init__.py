from repro.models.transformer import (decode_run, decode_step, extend,
                                      extend_row, forward, init_cache,
                                      init_params, layout, prefill)
from repro.models.kvcache import (cache_bytes, copy_into_prefix,
                                  copy_prefix_rows, dequantize_kv,
                                  handoff_row, kv_supports_int8,
                                  paste_prefix, quantize_kv, read_row,
                                  reset_row, select_rows, snapshot_prefix,
                                  truncate_rings, write_row_slice,
                                  write_slot)
from repro.models.params import (batch_pspec, cache_pspecs, param_pspecs,
                                 param_shardings)

__all__ = ["cache_bytes", "copy_into_prefix", "copy_prefix_rows",
           "decode_run", "decode_step", "dequantize_kv", "extend",
           "extend_row", "forward", "handoff_row", "init_cache",
           "init_params", "kv_supports_int8", "layout", "paste_prefix",
           "prefill", "quantize_kv", "read_row", "reset_row", "select_rows",
           "snapshot_prefix", "truncate_rings", "write_row_slice",
           "write_slot", "batch_pspec", "cache_pspecs", "param_pspecs",
           "param_shardings"]
