// paddle_tpu inference C API — embedded-CPython implementation.
//
// Reference: paddle/fluid/inference/capi/pd_predictor.cc (C shims over the
// C++ AnalysisPredictor). The TPU build's predictor is the Python-side
// shape-cached XLA executor, so this library embeds the interpreter once
// per process and marshals tensors through numpy. All Python access is
// GIL-guarded; error text is captured per thread for PD_GetLastError.
#include "inference_c.h"

#include <Python.h>

#include <cstring>
#include <string>
#include <vector>

namespace {

thread_local std::string g_last_error;

void set_error_from_python() {
  PyObject *type = nullptr, *value = nullptr, *tb = nullptr;
  PyErr_Fetch(&type, &value, &tb);
  PyErr_NormalizeException(&type, &value, &tb);
  g_last_error = "python error";
  if (value) {
    PyObject* s = PyObject_Str(value);
    if (s) {
      const char* c = PyUnicode_AsUTF8(s);
      if (c) g_last_error = c;
      Py_DECREF(s);
    }
  }
  Py_XDECREF(type);
  Py_XDECREF(value);
  Py_XDECREF(tb);
}

// Initialize the interpreter on first use. If PADDLE_TPU_C_PLATFORM is set
// (e.g. "cpu" in tests), pin jax to that platform before any backend touch.
bool ensure_python() {
  if (!Py_IsInitialized()) {
    Py_InitializeEx(0);
    if (!Py_IsInitialized()) {
      g_last_error = "Py_Initialize failed";
      return false;
    }
    PyGILState_STATE gil = PyGILState_Ensure();
    const char* bootstrap =
        "import os\n"
        "_p = os.environ.get('PADDLE_TPU_C_PLATFORM')\n"
        "if _p:\n"
        "    os.environ['JAX_PLATFORMS'] = _p\n"
        "    import jax\n"
        "    jax.config.update('jax_platforms', _p)\n";
    if (PyRun_SimpleString(bootstrap) != 0) {
      g_last_error = "bootstrap failed";
      PyGILState_Release(gil);
      return false;
    }
    PyGILState_Release(gil);
    // hand the GIL to the GIL-state machinery (we re-acquire per call)
    PyEval_SaveThread();
  }
  return true;
}

const char* dtype_name(PD_DType dt) {
  switch (dt) {
    case PD_DTYPE_FLOAT32: return "float32";
    case PD_DTYPE_INT64: return "int64";
    case PD_DTYPE_INT32: return "int32";
  }
  return "float32";
}

}  // namespace

struct PD_Predictor {
  PyObject* predictor = nullptr;   // paddle_tpu.inference.Predictor
  PyObject* feeds = nullptr;       // dict name -> np array
  PyObject* results = nullptr;     // dict name -> np array (after Run)
  std::vector<std::string> input_names;
  std::vector<std::string> output_names;
};

extern "C" {

PD_Predictor* PD_NewPredictor(const char* model_prefix) {
  if (!ensure_python()) return nullptr;
  PyGILState_STATE gil = PyGILState_Ensure();
  PD_Predictor* out = nullptr;
  PyObject* mod = PyImport_ImportModule("paddle_tpu.inference");
  PyObject *cfg = nullptr, *pred = nullptr, *names = nullptr;
  if (!mod) goto fail;
  cfg = PyObject_CallMethod(mod, "Config", "s", model_prefix);
  if (!cfg) goto fail;
  pred = PyObject_CallMethod(mod, "create_predictor", "O", cfg);
  if (!pred) goto fail;

  out = new PD_Predictor();
  out->predictor = pred;
  out->feeds = PyDict_New();
  names = PyObject_CallMethod(pred, "get_input_names", nullptr);
  if (!names) goto fail;
  for (Py_ssize_t i = 0; i < PyList_Size(names); ++i)
    out->input_names.emplace_back(
        PyUnicode_AsUTF8(PyList_GetItem(names, i)));
  Py_DECREF(names);
  names = PyObject_CallMethod(pred, "get_output_names", nullptr);
  if (!names) goto fail;
  for (Py_ssize_t i = 0; i < PyList_Size(names); ++i)
    out->output_names.emplace_back(
        PyUnicode_AsUTF8(PyList_GetItem(names, i)));
  Py_DECREF(names);
  Py_DECREF(cfg);
  Py_DECREF(mod);
  PyGILState_Release(gil);
  return out;

fail:
  set_error_from_python();
  Py_XDECREF(cfg);
  Py_XDECREF(mod);
  if (out) {
    Py_XDECREF(out->feeds);
    Py_XDECREF(out->predictor);
    delete out;
  } else {
    Py_XDECREF(pred);
  }
  PyGILState_Release(gil);
  return nullptr;
}

void PD_DeletePredictor(PD_Predictor* pred) {
  if (!pred) return;
  PyGILState_STATE gil = PyGILState_Ensure();
  Py_XDECREF(pred->predictor);
  Py_XDECREF(pred->feeds);
  Py_XDECREF(pred->results);
  PyGILState_Release(gil);
  delete pred;
}

int PD_PredictorGetInputNum(PD_Predictor* p) {
  return p ? static_cast<int>(p->input_names.size()) : -1;
}
int PD_PredictorGetOutputNum(PD_Predictor* p) {
  return p ? static_cast<int>(p->output_names.size()) : -1;
}
const char* PD_PredictorGetInputName(PD_Predictor* p, int i) {
  if (!p || i < 0 || i >= static_cast<int>(p->input_names.size()))
    return nullptr;
  return p->input_names[i].c_str();
}
const char* PD_PredictorGetOutputName(PD_Predictor* p, int i) {
  if (!p || i < 0 || i >= static_cast<int>(p->output_names.size()))
    return nullptr;
  return p->output_names[i].c_str();
}

int PD_PredictorSetInput(PD_Predictor* p, const char* name, const void* data,
                         const int64_t* shape, int ndim, PD_DType dtype) {
  if (!p) return -1;
  PyGILState_STATE gil = PyGILState_Ensure();
  int rc = -1;
  int64_t numel = 1;
  for (int i = 0; i < ndim; ++i) numel *= shape[i];
  int64_t isize = dtype == PD_DTYPE_FLOAT32 ? 4
                  : dtype == PD_DTYPE_INT32 ? 4 : 8;
  PyObject *np = nullptr, *bytes = nullptr, *flat = nullptr,
           *shp = nullptr, *arr = nullptr;
  np = PyImport_ImportModule("numpy");
  if (!np) goto done;
  bytes = PyBytes_FromStringAndSize(static_cast<const char*>(data),
                                    numel * isize);
  if (!bytes) goto done;
  flat = PyObject_CallMethod(np, "frombuffer", "Os", bytes,
                             dtype_name(dtype));
  if (!flat) goto done;
  shp = PyTuple_New(ndim);
  for (int i = 0; i < ndim; ++i)
    PyTuple_SetItem(shp, i, PyLong_FromLongLong(shape[i]));
  arr = PyObject_CallMethod(flat, "reshape", "O", shp);
  if (!arr) goto done;
  if (PyDict_SetItemString(p->feeds, name, arr) == 0) rc = 0;

done:
  if (rc != 0) set_error_from_python();
  Py_XDECREF(arr);
  Py_XDECREF(shp);
  Py_XDECREF(flat);
  Py_XDECREF(bytes);
  Py_XDECREF(np);
  PyGILState_Release(gil);
  return rc;
}

int PD_PredictorRun(PD_Predictor* p) {
  if (!p) return -1;
  PyGILState_STATE gil = PyGILState_Ensure();
  int rc = -1;
  // results = {name: np.asarray(v) for name, v in
  //            zip(output_names, predictor.run([feeds[n] for n in inputs]))}
  PyObject *feed_list = nullptr, *outs = nullptr, *np = nullptr,
           *results = nullptr;
  feed_list = PyList_New(0);
  for (const auto& n : p->input_names) {
    PyObject* v = PyDict_GetItemString(p->feeds, n.c_str());  // borrowed
    if (!v) {
      g_last_error = "input '" + n + "' was not set";
      goto done;
    }
    PyList_Append(feed_list, v);
  }
  outs = PyObject_CallMethod(p->predictor, "run", "O", feed_list);
  if (!outs) { set_error_from_python(); goto done; }
  np = PyImport_ImportModule("numpy");
  if (!np) { set_error_from_python(); goto done; }
  results = PyDict_New();
  for (size_t i = 0; i < p->output_names.size(); ++i) {
    PyObject* item = PySequence_GetItem(outs, static_cast<Py_ssize_t>(i));
    if (!item) { set_error_from_python(); goto done; }
    PyObject* arr = PyObject_CallMethod(np, "ascontiguousarray", "O", item);
    Py_DECREF(item);
    if (!arr) { set_error_from_python(); goto done; }
    PyDict_SetItemString(results, p->output_names[i].c_str(), arr);
    Py_DECREF(arr);
  }
  Py_XDECREF(p->results);
  p->results = results;
  results = nullptr;
  rc = 0;

done:
  Py_XDECREF(results);
  Py_XDECREF(np);
  Py_XDECREF(outs);
  Py_XDECREF(feed_list);
  PyGILState_Release(gil);
  return rc;
}

static PyObject* get_result(PD_Predictor* p, const char* name) {
  if (!p || !p->results) return nullptr;
  return PyDict_GetItemString(p->results, name);  // borrowed
}

int PD_PredictorGetOutputNumDims(PD_Predictor* p, const char* name) {
  PyGILState_STATE gil = PyGILState_Ensure();
  int nd = -1;
  PyObject* arr = get_result(p, name);
  if (arr) {
    PyObject* ndim = PyObject_GetAttrString(arr, "ndim");
    if (ndim) {
      nd = static_cast<int>(PyLong_AsLong(ndim));
      Py_DECREF(ndim);
    }
  } else {
    g_last_error = "no result for output (did PD_PredictorRun succeed?)";
  }
  PyGILState_Release(gil);
  return nd;
}

int PD_PredictorGetOutputShape(PD_Predictor* p, const char* name,
                               int64_t* shape) {
  PyGILState_STATE gil = PyGILState_Ensure();
  int rc = -1;
  PyObject* arr = get_result(p, name);
  if (arr) {
    PyObject* shp = PyObject_GetAttrString(arr, "shape");
    if (shp) {
      for (Py_ssize_t i = 0; i < PyTuple_Size(shp); ++i)
        shape[i] = PyLong_AsLongLong(PyTuple_GetItem(shp, i));
      rc = 0;
      Py_DECREF(shp);
    }
  }
  PyGILState_Release(gil);
  return rc;
}

int PD_PredictorCopyOutput(PD_Predictor* p, const char* name, void* dst,
                           int64_t nbytes) {
  PyGILState_STATE gil = PyGILState_Ensure();
  int rc = -1;
  PyObject* arr = get_result(p, name);
  if (arr) {
    PyObject* tob = PyObject_CallMethod(arr, "tobytes", nullptr);
    if (tob) {
      char* buf = nullptr;
      Py_ssize_t len = 0;
      if (PyBytes_AsStringAndSize(tob, &buf, &len) == 0) {
        if (len > nbytes) {
          g_last_error = "output larger than destination buffer";
        } else {
          std::memcpy(dst, buf, static_cast<size_t>(len));
          rc = 0;
        }
      }
      Py_DECREF(tob);
    }
  }
  PyGILState_Release(gil);
  return rc;
}

const char* PD_GetLastError(void) { return g_last_error.c_str(); }

}  // extern "C"
