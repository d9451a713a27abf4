ALIAS_OF = "verdict_wake_ms"
