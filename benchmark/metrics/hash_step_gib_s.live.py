ALIAS_OF = "hash_step_gib_s"
