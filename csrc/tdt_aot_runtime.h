/* tdt_aot_runtime — native loader + executor for triton_distributed_tpu
 * AOT bundles.
 *
 * Reference analogue: python/triton_dist/tools/runtime/
 * triton_aot_runtime.{h,cc} (CUDA-driver module/kernel loader,
 * multi-context safe).  Here the artifact is a jax.export StableHLO
 * bundle (see tools/compile_aot.py): the loader parses bundles
 * natively, and the executor compiles the bundled StableHLO through
 * the PJRT C API of any plugin .so (libtpu, ...) and
 * runs it — native deployment with no Python in the loop.  Pure C ABI
 * so it is usable from C, C++ and Python ctypes.
 */
#ifndef TDT_AOT_RUNTIME_H_
#define TDT_AOT_RUNTIME_H_

#include <stddef.h>
#include <stdint.h>

#ifdef __cplusplus
extern "C" {
#endif

typedef enum tdt_status {
  TDT_OK = 0,
  TDT_ERR_IO = 1,
  TDT_ERR_FORMAT = 2,
  TDT_ERR_NOT_FOUND = 3,
  TDT_ERR_NO_BACKEND = 4,
  TDT_ERR_PJRT = 5,
} tdt_status;

/* Matches tools/native.py _DTYPE_CODES. */
typedef enum tdt_dtype {
  TDT_F32 = 0,
  TDT_BF16 = 1,
  TDT_F16 = 2,
  TDT_I32 = 3,
  TDT_I64 = 4,
  TDT_U8 = 5,
  TDT_I8 = 6,
  TDT_BOOL = 7,
} tdt_dtype;

#define TDT_MAX_RANK 8

typedef struct tdt_sig {
  uint8_t dtype; /* tdt_dtype */
  uint8_t rank;
  int64_t dims[TDT_MAX_RANK];
} tdt_sig;

typedef struct tdt_bundle tdt_bundle;
typedef struct tdt_executable tdt_executable;
typedef struct tdt_client tdt_client;
typedef struct tdt_compiled tdt_compiled;

/* ---- bundle loading (index.bin v2, written by compile_aot) ---- */

tdt_status tdt_bundle_open(const char* path, tdt_bundle** out);
void tdt_bundle_close(tdt_bundle* b);

int tdt_bundle_num_variants(const tdt_bundle* b);
const char* tdt_bundle_variant_name(const tdt_bundle* b, int i);

/* Argument/output signatures of a variant (NULL if out of range). */
int tdt_bundle_variant_arity(const tdt_bundle* b, const char* variant,
                             int* nargs, int* nouts);
const tdt_sig* tdt_bundle_arg_sig(const tdt_bundle* b, const char* variant,
                                  int i);
const tdt_sig* tdt_bundle_out_sig(const tdt_bundle* b, const char* variant,
                                  int i);

/* Runtime variant selection: return the name of the first variant
 * whose argument signatures match (dtype, rank, dims) exactly, or
 * NULL.  The C-side analogue of shape-keyed kernel dispatch for
 * bundles that declare one variant per tuned shape. */
const char* tdt_bundle_select_variant(const tdt_bundle* b, int nargs,
                                      const tdt_sig* sigs);

/* Load one variant's serialized jax.export payload into memory. */
tdt_status tdt_bundle_load_variant(tdt_bundle* b, const char* variant,
                                   tdt_executable** out);
void tdt_executable_free(tdt_executable* e);
const uint8_t* tdt_executable_bytes(const tdt_executable* e);
size_t tdt_executable_size(const tdt_executable* e);

/* ---- native execution through the PJRT C API ---- */

/* One client-create option (becomes a PJRT_NamedValue). */
typedef struct tdt_option {
  const char* name;
  const char* str_value; /* used when is_int == 0 */
  int64_t int_value;     /* used when is_int == 1 */
  int is_int;
} tdt_option;

/* dlopen `plugin_so`, resolve GetPjrtApi, initialize the plugin and
 * create a client with the given options. */
tdt_status tdt_client_create(const char* plugin_so, const tdt_option* opts,
                             int nopts, tdt_client** out);
void tdt_client_destroy(tdt_client* c);

/* Compile a bundle variant's StableHLO (<name>__<variant>.mlirbc +
 * compile_options.pb) for this client. */
tdt_status tdt_client_compile(tdt_client* c, tdt_bundle* b,
                              const char* variant, tdt_compiled** out);
void tdt_compiled_free(tdt_compiled* e);

/* Synchronous execute: `args[i]` are dense host buffers matching the
 * variant's arg signatures; `outs[i]` are caller-allocated host
 * buffers sized per the output signatures. */
tdt_status tdt_compiled_execute(tdt_compiled* e, const void** args,
                                void** outs);

size_t tdt_sig_bytes(const tdt_sig* s);
const char* tdt_status_str(tdt_status s);
/* Message of the most recent TDT_ERR_PJRT on this thread. */
const char* tdt_last_error(void);

#ifdef __cplusplus
}
#endif

#endif /* TDT_AOT_RUNTIME_H_ */
