ALIAS_OF = "reply_ms"
