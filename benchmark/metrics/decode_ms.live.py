ALIAS_OF = "decode_ms"
