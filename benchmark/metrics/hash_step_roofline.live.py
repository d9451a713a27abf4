ALIAS_OF = "hash_step_roofline"
